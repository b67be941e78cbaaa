"""Reduce a profiler trace (``.xplane.pb``) to device busy time, kernel
time and the host's spans.

Device planes are those named ``/device:TPU:<n>``; their ``XLA Ops`` line
holds one event per operation run on the chip, and their ``XLA Modules``
line one event per program run, named by the program and its fingerprint
(``jit__lambda(1640...)``).  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events, every host event whose name starts
with ``bench.``.

Busy time is the union of the operation intervals; a chip's idle share is
one minus busy over the window (the ``bench.window`` span).

Device time goes to a host call (decode, prefill) by the program the call
launches, not by the host's clock: a call returns before its program runs,
and the two clocks differ by some tenths of a millisecond.  The program of
a call is the one that ran as many times as the call was made while the
trace ran and took the most device time among those (``assign``).
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["Trace", "merge", "overlap", "total", "find_xplane", "short_name",
           "program_name"]

Interval = Tuple[float, float]
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


_CONTROL = {"while", "conditional", "call"}


def short_name(name: str) -> str:
    """``%fusion.152 = bf16[...] fusion(...)`` -> ``fusion.152``."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def program_name(module: str) -> str:
    """``jit__lambda(1640...)`` -> ``jit__lambda``."""
    return module.split("(", 1)[0]


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals, sorted and disjoint."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def overlap(xs: Sequence[Interval], ys: Sequence[Interval]) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(xs) and j < len(ys):
        a = max(xs[i][0], ys[j][0])
        b = min(xs[i][1], ys[j][1])
        if b > a:
            acc += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return acc


def clip(xs: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in xs if b > lo and a < hi]


class Trace:
    """Operations per chip and the host's spans, in nanoseconds.

    ``ops[chip]`` and ``modules[chip]`` are lists of ``(start, end,
    name)``; ``labels`` maps an operation's name to the text its stats carry
    (the HLO module and the operation's long name), read once per distinct
    name."""

    def __init__(self, path: Path):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(str(path))
        self.ops: Dict[int, List[Tuple[float, float, str]]] = {}
        self.modules: Dict[int, List[Tuple[float, float, str]]] = {}
        self.labels: Dict[str, str] = {}
        self.spans: Dict[str, List[Interval]] = defaultdict(list)
        for plane in data.planes:
            m = _DEVICE.match(plane.name)
            if m:
                chip = int(m.group(1))
                self.ops[chip] = self._read_ops(plane)
                self.modules[chip] = sorted(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for line in plane.lines if line.name == MODULES_LINE
                    for ev in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            self.spans[ev.name].append(
                                (ev.start_ns, ev.start_ns + ev.duration_ns))
        for name in self.spans:
            self.spans[name].sort()
        self._by_name = {name: (ivs, [a for a, _ in ivs])
                         for name, ivs in self.spans.items()
                         if name != SPAN_PREFIX + "window"}

    def _read_ops(self, plane) -> List[Tuple[float, float, str]]:
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                name = ev.name
                if name not in self.labels:
                    self.labels[name] = name + " " + " ".join(
                        str(v) for _, v in ev.stats if isinstance(v, str))
                ops.append((ev.start_ns, ev.start_ns + ev.duration_ns, name))
        ops.sort()
        return ops

    # -- windows and spans --------------------------------------------------
    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def window(self) -> Optional[Interval]:
        """The ``bench.window`` span (the measured window), if traced."""
        w = self.spans.get(SPAN_PREFIX + "window")
        return (w[0][0], w[-1][1]) if w else None

    # -- device time ----------------------------------------------------------
    def busy(self, chip: int, window: Optional[Interval] = None
             ) -> List[Interval]:
        iv = merge([(a, b) for a, b, _ in self.ops[chip]])
        return clip(iv, window) if window else iv

    def busy_s(self, window: Interval) -> float:
        """Busy seconds in ``window``, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(total(self.busy(c, window)) for c in self.chips) \
            / len(self.chips) * 1e-9

    # -- programs -------------------------------------------------------------
    def runs(self, module: str) -> float:
        """Times ``module`` ran, averaged over the chips."""
        if not self.modules:
            return 0.0
        return sum(1 for c in self.chips for *_, n in self.modules[c]
                   if n == module) / len(self.chips)

    def program_time_s(self, module: str) -> float:
        """Device seconds of ``module``'s runs, averaged over the chips."""
        if not self.modules:
            return 0.0
        return sum(b - a for c in self.chips for a, b, n in self.modules[c]
                   if n == module) / len(self.chips) * 1e-9

    def assign(self, calls: Dict[str, int]) -> Dict[str, str]:
        """The program each kind of host call launches: ``calls`` gives how
        many calls of each kind were made while the trace ran.  A kind's
        program ran that many times (to 1%), and took the most device time
        among such programs not already given to a kind before it.  A kind
        with no such program is left out."""
        names = {n for c in self.chips for *_, n in self.modules[c]}
        out: Dict[str, str] = {}
        for kind, n in calls.items():
            fit = [m for m in names if m not in out.values()
                   and n > 0 and abs(self.runs(m) - n) <= max(1.0, 0.01 * n)]
            if fit:
                out[kind] = max(fit, key=self.program_time_s)
        return out

    def _module_at(self, chip: int, t: float) -> Optional[str]:
        mods = self.modules.get(chip, [])
        i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
        if i >= 0 and mods[i][0] <= t < mods[i][1]:
            return mods[i][2]
        return None

    def op_time_s(self, match: Callable[[str], bool],
                  window: Optional[Interval] = None) -> float:
        """Summed duration of the operations whose label ``match``es,
        averaged over the chips."""
        if not self.ops:
            return 0.0
        acc = 0.0
        for c in self.chips:
            for a, b, name in self.ops[c]:
                if window and (b <= window[0] or a >= window[1]):
                    continue
                if match(self.labels[name]):
                    acc += b - a
        return acc / len(self.chips) * 1e-9

    # -- breakdown ----------------------------------------------------------
    def innermost_span(self, t: float) -> str:
        """The shortest host span open at time ``t`` (spans of one name
        never overlap, so each name is searched by bisection), or
        ``host:none``."""
        best = None
        for name, (ivs, starts) in self._by_name.items():
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and ivs[i][1] >= t:
                length = ivs[i][1] - ivs[i][0]
                if best is None or length < best[0]:
                    best = (length, name)
        return best[1] if best else "host:none"

    def top_ops(self, window: Interval, kinds: Optional[Dict[str, str]] = None,
                k: int = 10) -> List[Tuple[str, float]]:
        """The ``k`` operations that took the most device time in the
        window (seconds, averaged over the chips), named by the program they
        ran in and their HLO instruction (``decode:fusion.152``): the kind
        of call ``kinds`` (from ``assign``) gives the program, else the
        program's name.  Control flow (``while``, ``conditional``,
        ``call``) encloses other operations and is left out."""
        by_module = {m: kind for kind, m in (kinds or {}).items()}
        acc: Dict[str, float] = defaultdict(float)
        for c in self.chips:
            for a, b, name in self.ops[c]:
                short = short_name(name)
                if short.split(".")[0] in _CONTROL:
                    continue
                if a < window[1] and b > window[0]:
                    mod = self._module_at(c, a)
                    prog = by_module.get(mod) or (program_name(mod) if mod
                                                  else "none")
                    label = prog + ":" + short
                    acc[label] += (min(b, window[1]) - max(a, window[0]))
        n = max(len(self.chips), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [(name, t / n * 1e-9) for name, t in top]

    def idle_gaps(self, window: Interval, k: int = 10
                  ) -> List[Tuple[str, float]]:
        """Idle device time in the window, by the innermost host span open
        at each gap's midpoint (``host:none`` where no span was open):
        the ``k`` largest totals, in seconds, averaged over the chips."""
        acc: Dict[str, float] = defaultdict(float)
        for c in self.chips:
            busy = self.busy(c, window)
            edges = [window[0]] + [x for iv in busy for x in iv] \
                + [window[1]]
            for g0, g1 in zip(edges[::2], edges[1::2]):
                if g1 > g0:
                    acc[self.innermost_span((g0 + g1) / 2)] += g1 - g0
        n = max(len(self.chips), 1)
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [(name, t / n * 1e-9) for name, t in top]
