"""One general generator for every traffic mix.

A mix (``bench/traffic/<name>.json``) gives length distributions and the
loop; a cell (``bench/cells/<workload>.json``) gives the engine's ring size
(``s_max``), prefill chunk, batch and, for an open loop, the rate.

Every seed gets the same *set* of sizes and arrival gaps, in another order:
sizes are stratified quantiles of the mix's distributions (a fixed
multiset) in one fixed cyclic order, and ``--seed`` only picks where in
that cycle the stream starts (and draws the prompt tokens).  So two seeds
do the same work, arranged as rotations of one trace: a fresh permutation
per seed would change which requests bunch together, and with it the tails
the benchmark measures.

Length distribution fields, in tokens: ``dist`` (``uniform`` over ``[lo,
hi]`` or ``lognormal`` with ``median`` and ``sigma``, clipped to ``[lo,
hi]``) and ``round_up`` (``"chunk"`` rounds up to a multiple of the prefill
chunk, at least one chunk; otherwise up to a whole token, at least 1).

Every mix is cut to fit the cell's ring, and only so: a prompt is at most
the whole chunks that leave one token or more of the ring free, and an
output at most what the ring leaves after its prompt.

Loops:

* ``closed``: ``clients_per_slot * max_batch`` clients, each sending its
  next request when its last one finishes.  The window opens on every slot
  holding a request caught mid-flight: its (prompt, output) pair is drawn
  length-biased by output, its age ``A`` uniform in ``[0, L)`` for output
  length ``L``, the part already generated is folded into its prompt
  (floored to whole chunks) and what is left to generate is ``L - A``, the
  residual life.  So slots finish at staggered times from the window's
  start, as they would in a loop that has run for a while.
* ``open``: ``floor(rate * seconds)`` Poisson arrivals at the cell's
  ``rate_per_s``, every one due inside the window.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np

__all__ = ["RequestSpec", "Traffic", "quantile_lengths", "seed_words"]

#: quantiles behind the "distinct lengths" and length-biased draws
_FINE = 4096


@dataclass
class RequestSpec:
    prompt_len: int
    output_len: int
    #: seconds after the window's start (open loop); 0 for closed loops
    due: float = 0.0


def seed_words(seed: int) -> List[int]:
    """``seed`` as two 32-bit words (seeds may exceed 32 bits)."""
    seed = int(seed) % (1 << 64)
    return [seed & 0xFFFFFFFF, seed >> 32]


def quantile_lengths(dist: dict, n: int, cell: dict) -> np.ndarray:
    """The stratified quantiles ``(k + 0.5) / n`` of ``dist``, clipped and
    rounded: a fixed multiset of ``n`` lengths, ascending."""
    q = (np.arange(n) + 0.5) / n
    if dist["dist"] == "uniform":
        x = dist["lo"] + q * (dist["hi"] - dist["lo"])
    elif dist["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(v)) for v in q])
        x = np.clip(dist["median"] * np.exp(dist["sigma"] * z),
                    dist["lo"], dist["hi"])
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    if dist.get("round_up") == "chunk":
        c = int(cell["prefill_chunk"])
        out = np.maximum(np.ceil(x / c - 1e-9), 1) * c
    else:
        out = np.maximum(np.ceil(x - 1e-9), 1)
    return out.astype(np.int64)


class Traffic:
    """The request stream of one cell and one seed."""

    def __init__(self, mix: dict, cell: dict, seed: int, seconds: float,
                 vocab: int):
        self.mix, self.cell = mix, cell
        self.seed = seed_words(seed)
        self.seconds = float(seconds)
        self.vocab = int(vocab)
        self.ring = int(cell["s_max"])
        self.chunk = int(cell["prefill_chunk"])
        self.max_batch = int(cell["max_batch"])
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self._order = np.random.default_rng([1] + self.seed)
        #: where the stream starts in its fixed cyclic order, as a share of
        #: the cycle
        self._start = float(self._order.random())
        if self.loop == "closed":
            self.clients = int(round(mix["clients_per_slot"]
                                     * self.max_batch))
            self._pop = self._population(int(mix["population"]))
            self._next = 0

    @property
    def prompt_cap(self) -> int:
        """The longest prompt the ring takes: whole chunks, one token or
        more left for the output."""
        return (self.ring - 1) // self.chunk * self.chunk

    # -- tokens -------------------------------------------------------------
    def tokens(self, index: int, length: int, stream: int = 2) -> np.ndarray:
        """Prompt tokens of request ``index``: uniform over the vocabulary,
        from their own stream of the seed (``stream`` 3 is the warm-up)."""
        rng = np.random.default_rng([stream, index] + self.seed)
        return rng.integers(0, self.vocab, size=length, dtype=np.int32)

    # -- shapes -------------------------------------------------------------
    def distinct_prompt_lengths(self) -> List[int]:
        return sorted({r.prompt_len for r in self._pairs(_FINE)})

    def warmup(self) -> List[RequestSpec]:
        """Every distinct prompt length once, then the shortest until every
        slot is taken (so every slot index, every prompt length and the
        full decode batch run before the window), two tokens each."""
        lens = self.distinct_prompt_lengths()
        lens += [lens[0]] * max(0, self.max_batch - len(lens))
        return [RequestSpec(n, 2) for n in lens]

    # -- closed loop --------------------------------------------------------
    def _pairs(self, n: int) -> List[RequestSpec]:
        """``n`` (prompt, output) pairs: quantiles of each, paired the same
        way for every seed, then cut to fit the ring (see the module doc)."""
        p = quantile_lengths(self.mix["prompt"], n, self.cell)
        o = quantile_lengths(self.mix["output"], n, self.cell)
        o = o[np.random.default_rng(0).permutation(n)]
        p = np.minimum(p, self.prompt_cap)
        o = np.minimum(o, self.ring - p)
        return [RequestSpec(int(a), int(b)) for a, b in zip(p, o)]

    def _rotated(self, n: int, stream: int) -> np.ndarray:
        """A fixed order of ``n`` items (the same for every seed), started
        at this seed's point of the cycle."""
        base = np.random.default_rng(stream).permutation(n)
        return np.roll(base, -int(self._start * n))

    def _population(self, n: int) -> List[RequestSpec]:
        pairs = self._pairs(n)
        return [pairs[i] for i in self._rotated(n, 2)]

    def next_request(self) -> RequestSpec:
        """The next request a closed-loop client sends."""
        r = self._pop[self._next % len(self._pop)]
        self._next += 1
        return RequestSpec(r.prompt_len, r.output_len)

    def first_wave(self) -> List[RequestSpec]:
        """``max_batch`` requests caught mid-flight (see the module doc):
        ``prompt_len`` includes the tokens already generated, floored to
        whole chunks, and ``output_len`` is the residual life."""
        b = self.max_batch
        fine = sorted(self._pairs(_FINE), key=lambda r: r.output_len)
        cum = np.cumsum([float(r.output_len) for r in fine])
        cum /= cum[-1]
        q = (np.arange(b) + 0.5) / b
        picks = [fine[i] for i in
                 np.minimum(np.searchsorted(cum, q), _FINE - 1)]
        age = (np.random.default_rng(0).permutation(b) + 0.5) / b
        wave = []
        for r, a in zip(picks, age):
            rem = max(1, int(round(r.output_len * (1.0 - a))))
            done = r.output_len - rem
            wave.append(RequestSpec(
                r.prompt_len + done // self.chunk * self.chunk, rem))
        return [wave[i] for i in self._order.permutation(b)]

    # -- open loop ------------------------------------------------------------
    def arrivals(self) -> List[RequestSpec]:
        """Poisson arrivals at ``rate_per_s``: stratified exponential gaps
        scaled to a mean of exactly ``1 / rate``, so every arrival is due
        inside the window; the (prompt, output) pairs in a fixed order of
        their own, both rotated to this seed's start."""
        rate = float(self.cell["rate_per_s"])
        n = max(1, int(math.floor(rate * self.seconds)))
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q)
        gaps *= n / rate / gaps.sum()
        gaps = gaps[self._rotated(n, 3)]
        due = np.cumsum(gaps) - gaps[0]
        pairs = self._pairs(n)
        pairs = [pairs[i] for i in self._rotated(n, 4)]
        return [RequestSpec(r.prompt_len, r.output_len, float(t))
                for r, t in zip(pairs, due)]
