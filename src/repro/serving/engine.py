"""Paged-KV continuous-batching serving engine.

The strategy scheduler (``core/device/request_scheduler``) decides *what*
runs each step — admission by priority, dead-request eviction, merged and
chunked prefills; this engine executes the plan against the model.

Two KV layouts (``kv_mode``):

* ``"paged"`` (default where the family supports it) — a shared physical
  pool of fixed-size KV blocks with per-request block tables
  (``serving.paged_kv``).  Blocks are allocated on demand as a request's
  context grows, admission is a *memory* decision (``free_tokens``), long
  prompts prefill in chunks that re-enter the strategy queue between chunks
  (an urgent arrival overtakes a half-prefilled bulk prompt; a thief steals
  it *with* its processed KV blocks), and pool pressure preempts
  (recompute) the least urgent holder instead of refusing admission.
  Decode reads K/V through the block table — bit-identical (fp32) to the
  contiguous path because the gathered logical view has the same width,
  mask and values.
* ``"contiguous"`` — the dense per-slot ``[B, S_max]`` cache (SSM/enc-dec
  families, and the equality-gate baseline).

Works with any family whose cache pytree carries the batch on a fixed axis
(dense/MoE/VLM: axis 1 of [L, B, S, ...]; RWKV: axis 1).  CPU-runnable with
reduced configs — that is how the examples and tests drive it.

Each phase of :meth:`ServingEngine.step` is a ``jax.profiler`` span named
``serve.*`` (``serve.step`` > ``plan``, ``prefill``, ``speculate``,
``blocks``, ``decode``, ``wait``, ``commit``); outside a profiler trace a
span costs about a microsecond.  ``docs/serving.md`` lists their stats.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..core.device.request_scheduler import (AdmissionRejected, BatchPlan,
                                             ContinuousBatcher, Request,
                                             RequestState)
from ..core.strategy import MergePolicy
from ..kernels.flash_attention.decode import decode_kv_dtype, decode_tile
from ..models.model_zoo import Model
from .paged_kv import (BlockAllocator, PoolExhausted, SINK_BLOCK,
                       jit_pool_program, prefix_block_keys)
from .speculative import Speculator

__all__ = ["ServingEngine"]

#: JAX reports every program it compiles, or loads from the persistent
#: cache, with this duration event, on the thread that asked for it
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_log = threading.local()


def _compiled() -> int:
    """Programs compiled or loaded on this thread so far."""
    return getattr(_compile_log, "n", 0)


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        _compile_log.n = _compiled() + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


class ServingEngine:
    def __init__(self, model: Model, params, *, max_batch: int = 4,
                 s_max: int = 128, prefill_token_budget: int = 512,
                 batch_axis: int = 1, eos_token: Optional[int] = None,
                 merge_policy: Optional[MergePolicy] = None,
                 kv_mode: str = "auto", block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 admission: str = "strategy",
                 prefix_cache: bool = False,
                 overflow: str = "reject",
                 speculator: Optional[Speculator] = None,
                 device: Optional[jax.Device] = None):
        if kv_mode not in ("auto", "paged", "contiguous"):
            raise ValueError(f"unknown kv_mode {kv_mode!r}")
        if overflow not in ("reject", "truncate", "allow"):
            raise ValueError(f"unknown overflow policy {overflow!r}")
        if kv_mode == "paged" and not model.supports_paged:
            raise ValueError(
                f"family {model.cfg.family!r} has no paged decode path")
        if kv_mode == "auto":
            kv_mode = "paged" if model.supports_paged else "contiguous"
        #: device holding this engine's params and KV pool (None = JAX's
        #: default device); the jitted steps run where their committed
        #: inputs live, so replicas pinned to different chips never meet
        self.device = device
        #: the ``device`` stat of every ``serve.step`` span
        self._device_id = (device if device is not None
                           else jax.devices()[0]).id
        if device is not None:
            params = jax.device_put(params, device)
        self.model = model
        self.params = params
        self.s_max = s_max
        self.batch_axis = batch_axis
        self.eos = eos_token
        self.kv_mode = kv_mode
        self.paged = kv_mode == "paged"
        # chunked prefill only where the model has a chunk kernel (pure
        # attention trunks; hybrid needs Mamba state carry across chunks)
        chunk = prefill_chunk if (self.paged and
                                  model.prefill_chunk_paged is not None) \
            else None
        self.batcher = ContinuousBatcher(
            max_batch=max_batch, prefill_token_budget=prefill_token_budget,
            merge_policy=merge_policy, prefill_chunk=chunk,
            admission=admission)
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.slot_pos = np.zeros(max_batch, np.int64)
        #: each slot's last token, kept on the host (the tokens are read
        #: back anyway) and uploaded once per decode
        self.last_token = np.zeros((max_batch, 1), np.int32)
        self.outputs: Dict[int, List[int]] = {}
        self.prompts: Dict[int, np.ndarray] = {}
        #: prefill requests of the CURRENT plan not yet executed — popped
        #: out of the waiting storage, so the preemption victim scan must
        #: see them separately (else a plan whose members jointly hold the
        #: whole pool deadlocks: everyone defers to invisible holders)
        self._pending_prefill: List[Request] = []
        # jit per distinct prompt length (lengths repeat across requests);
        # jitting the model's own function lets engines that share a model
        # share its compiled programs
        self._prefill = jax.jit(model.prefill, static_argnums=2)
        self._prefill_chunk = None
        cfg = model.cfg
        #: ring capacity of the KV cache (window-clamped); SSM families have
        #: no KV ring at all
        self.cap = s_max if cfg.sliding_window is None \
            else min(s_max, cfg.sliding_window)
        #: tokens per KV tile of the flash decode kernel over this ring
        #: (``serve.decode`` stat ``kv_tiles``)
        kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
        self.kv_tile = decode_tile(
            self.cap, kvh * hd, decode_kv_dtype(kvh, hd, cfg.dtype).itemsize)
        # A full-attention ring cannot evict: a request whose
        # prompt + budget exceeds the capacity wraps and corrupts its own
        # earliest KV (models/attention.py paged-prefill contract requires
        # start + c <= cap).  Sliding-window rings evict by design, SSM
        # state is O(1) — neither needs the admission check.
        self.overflow = overflow
        self._enforce_fit = (cfg.sliding_window is None
                             and cfg.family != "ssm"
                             and overflow != "allow")
        # Prefix caching shares immutable full prompt blocks between
        # requests; it needs the chunk kernel to resume behind an adopted
        # prefix (pure-attention trunks only — the hybrid's Mamba states are
        # not content-addressable).
        self.prefix_cache = bool(prefix_cache and kv_mode == "paged"
                                 and model.prefill_chunk_paged is not None)
        self._keys: Dict[int, list] = {}     # rid -> chained block keys
        self.cache_stats = {"hit_tokens": 0, "miss_tokens": 0,
                            "hit_requests": 0, "lookup_requests": 0}
        #: rids whose current prefill cycle already hit the stats (a
        #: requeued-then-retried cold request must not count twice; a
        #: preemption releases the rid and legitimately re-counts)
        self._stat_seen: set = set()
        #: (token_bytes, keys) memo: a cache-affinity router probes several
        #: replicas with the same prompt and then submits it — hash the
        #: chain once, not once per probe.  Keyed by content (a memcmp),
        #: not object identity: a caller reusing a mutated buffer must
        #: never get the previous prompt's keys.
        self._hash_memo: Optional[Tuple[bytes, list]] = None
        if self.paged:
            if self.cap % block_size:
                raise ValueError(f"KV capacity {self.cap} not divisible by "
                                 f"block_size {block_size}")
            self.block_size = block_size
            self.max_blocks = self.cap // block_size
            if num_blocks is None:
                # same physical memory as the dense cache (+ the sink)
                num_blocks = max_batch * self.max_blocks + 1
            if num_blocks < self.max_blocks + 1:
                raise ValueError("pool smaller than one full ring: "
                                 f"{num_blocks - 1} < {self.max_blocks}")
            self.alloc = BlockAllocator(num_blocks, block_size)
            with jax.default_device(device):
                self.cache = model.init_paged_cache(max_batch, num_blocks,
                                                    block_size)
            self.table = np.full((max_batch, self.max_blocks), SINK_BLOCK,
                                 np.int32)
            # device-side table cache: re-uploaded only when the allocator
            # or a slot assignment changed (most decode steps change
            # neither)
            self._table_dev = jnp.asarray(self.table)
            self._alloc_seen = self.alloc.version
            self._table_dirty = False
            self._decode = jit_pool_program(model.decode_step_paged)
            self._insert_prefill = jit_pool_program(
                model.insert_prefill_paged)
            self._prefill_chunk = (
                jit_pool_program(model.prefill_chunk_paged)
                if model.prefill_chunk_paged else None)
            # prompts longer than the ring must take the ring-aligning
            # dense prefill (chunks would wrap mid-prompt)
            def _chunk_eligible(r):
                return r.prompt_len + 1 <= self.cap
            self.batcher.chunk_eligible = _chunk_eligible
            self.batcher.on_request_pruned = self._on_pruned
        else:
            with jax.default_device(device):
                self.cache = model.init_cache(max_batch, s_max)
            self._decode = jax.jit(model.decode_step)
            self._insert = (jax.jit(model.insert_prefill)
                            if model.insert_prefill is not None else None)
        # speculative decoding: a draft model proposes, this model verifies
        # (attach validates the pairing — paged target, matching vocab)
        self.speculator = speculator
        if speculator is not None:
            speculator.attach(self)

    # -- client API ----------------------------------------------------------
    def _fit_or_raise(self, prompt_len: int, max_new: int,
                      can_reject: bool, generated: int = 0) -> int:
        """Capacity admission check: the prompt plus the *remaining* token
        budget must fit the KV ring or the earliest prompt blocks get
        silently overwritten mid-generation (a preempted request's emitted
        tokens are folded into its prompt, but decode only needs
        ``max_new - generated`` more positions).  Returns the (possibly
        truncated) token budget; raises on reject.  Either path bumps a
        telemetry counter."""
        if not self._enforce_fit \
                or prompt_len + max_new - generated <= self.cap:
            return max_new
        if self.overflow == "reject" and can_reject:
            self.batcher.metrics["rejected"] += 1
            raise AdmissionRejected(
                f"prompt_len + remaining budget = "
                f"{prompt_len + max_new - generated} exceeds KV capacity "
                f"{self.cap}: the ring would wrap and corrupt the prompt's "
                "own earliest blocks (use overflow='truncate'/'allow' to "
                "override)")
        if prompt_len + 1 > self.cap:
            # not even the prompt fits — truncation cannot save it
            if can_reject:
                self.batcher.metrics["rejected"] += 1
                raise AdmissionRejected(
                    f"prompt of {prompt_len} tokens exceeds KV capacity "
                    f"{self.cap}")
            # migrated: already accepted by the cluster and truncation
            # cannot save it — serve degraded through the legacy
            # ring-aligning wrap path rather than drop the request
            self.batcher.metrics["wrapped_oversize"] += 1
            return max_new
        self.batcher.metrics["truncated"] += 1
        return generated + (self.cap - prompt_len)

    def _adoptable_keys(self, req: Request) -> list:
        """The prompt's adoptable chain: capped one token short of the
        prompt — the final token must always be prefilled to produce the
        first logits."""
        keys = self._keys.get(req.rid, [])
        return keys[:(req.prompt_len - 1) // self.block_size]

    def _probe_prefix(self, req: Request, tokens) -> None:
        """Hash the prompt's full blocks and record how much of it the local
        prefix cache covers (drives cache-aware admission / steal weight).
        A request that already holds prefill progress (imported KV) cannot
        adopt — its cached_prefix must not claim a chain it will never use,
        or cache-aware pricing undercounts its real remaining work."""
        if not self.prefix_cache:
            return
        self._keys[req.rid] = self._prompt_keys(tokens)
        if req.prefilled == 0:
            req.cached_prefix = \
                self.alloc.match_prefix(self._adoptable_keys(req)) \
                * self.block_size

    def submit(self, tokens: np.ndarray, max_new_tokens: int,
               priority: float = 1.0,
               deadline: Optional[float] = None) -> Request:
        if len(tokens) == 0:
            # a zero-prefill request would be admitted straight into the
            # running set with no slot, logits or last token to decode from
            raise ValueError("empty prompt")
        max_new_tokens = self._fit_or_raise(len(tokens), max_new_tokens,
                                            can_reject=True)
        req = Request(prompt_len=len(tokens), max_new_tokens=max_new_tokens,
                      priority=priority, deadline=deadline)
        self.prompts[req.rid] = np.asarray(tokens, np.int32)
        self.outputs[req.rid] = []
        self._probe_prefix(req, tokens)
        self.batcher.submit(req)
        return req

    def submit_request(self, req: Request, payload: Any = None,
                       migrated: bool = False) -> None:
        """Register an externally-created request (cluster router placement
        or, with ``migrated=True``, a steal migration from another
        replica).  ``payload`` is the prompt tokens, or a dict
        ``{"tokens": ..., "kv": (k, v), "outputs": [...]}`` when a
        partially-prefilled (or previously preempted) request migrates with
        its processed KV blocks and the tokens it already emitted.  A first
        placement that cannot fit is rejected like a direct ``submit``
        (per the overflow policy); a migrated request was already accepted
        by the cluster, so it is truncated rather than bounced."""
        kv = None
        outputs: List[int] = []
        if isinstance(payload, dict):
            tokens = payload["tokens"]
            kv = payload.get("kv")
            outputs = list(payload.get("outputs", []))
        else:
            tokens = payload
        if tokens is None or len(tokens) == 0:
            raise ValueError("empty prompt")
        req.max_new_tokens = self._fit_or_raise(
            len(tokens), req.max_new_tokens, can_reject=not migrated,
            generated=req.generated)
        if req.state is not RequestState.WAITING:
            # crash replay: the previous owner died mid-flight and the
            # router re-placed the request here.  Admission needs a clean
            # WAITING entry; any prefill/decode progress claimed by the
            # dead engine is gone (the rewind itself happens in
            # Request.reset_for_replay — this is the engine-side guard)
            req.state = RequestState.WAITING
        self.prompts[req.rid] = np.asarray(tokens, np.int32)
        self.outputs[req.rid] = outputs or self.outputs.get(req.rid, [])
        if req.prefilled > 0:
            if self.paged and kv is not None and self._import_kv(req, kv):
                pass                        # prefix KV adopted into our pool
            else:
                req.prefilled = 0           # recompute the prefix
        # cache affinity does not travel (and did not survive a crash):
        # re-probe against OUR pool — a prefix chain published here by
        # earlier shared-prefix traffic is re-adopted at prefill, so a
        # replayed request re-prefills only the uncached remainder
        req.cached_prefix = 0
        self._probe_prefix(req, tokens)
        self.batcher.submit(req)

    def export_waiting(self, target_weight: Optional[int] = None,
                       count: Optional[int] = None):
        """Yield waiting requests (with their prompt tokens) to a thief.
        Partially-prefilled chunk requests migrate with their processed KV
        blocks (gathered out of the pool via their block table), so the
        thief resumes at the chunk boundary instead of recomputing."""
        if target_weight is not None:
            stolen = self.batcher.steal_waiting(target_weight)
        else:
            stolen = self.batcher.steal_waiting_count(count or 0)
        out = []
        for r in stolen:
            payload: Dict[str, Any] = {"tokens": self.prompts.pop(r.rid)}
            self._keys.pop(r.rid, None)
            if self.paged and r.prefilled > 0:
                kv = self._export_kv(r)
                if kv is not None:
                    payload["kv"] = kv
                else:
                    # the processed prefix cannot travel (hybrid pools: the
                    # Mamba state is not exportable; attention pools: blocks
                    # already reclaimed) — the thief restarts from chunk 0,
                    # and the on-the-wire work estimate must say so
                    r.prefilled = 0
            r.cached_prefix = 0              # affinity does not travel
            emitted = self.outputs.pop(r.rid, None)
            if emitted:
                # a previously-preempted request already emitted tokens
                # (folded into the prompt): the client-visible stream must
                # travel with it
                payload["outputs"] = emitted
            self._release(r.rid)
            out.append((r, payload if len(payload) > 1
                        else payload["tokens"]))
        return out

    # -- paged-pool bookkeeping ----------------------------------------------
    def _release(self, rid: int) -> None:
        if self.paged:
            self.alloc.release(rid)
        if self.speculator is not None:
            self.speculator.drop_request(rid)
        self._stat_seen.discard(rid)
        # block keys die with the blocks: finish/evict/preempt all come
        # through here, and the one resubmit path (_preempt_running)
        # re-probes immediately after — a long-running engine must not
        # accumulate one key list per request ever served
        self._keys.pop(rid, None)

    def _prompt_keys(self, tokens) -> list:
        """Chained block keys of ``tokens``, memoized on token content
        (the same prompt is probed per replica and then submitted; the
        memcmp hit is far cheaper than re-running the hash chain)."""
        raw = np.ascontiguousarray(np.asarray(tokens, np.int32)).tobytes()
        memo = self._hash_memo
        if memo is not None and memo[0] == raw:
            return memo[1]
        keys = prefix_block_keys(tokens, self.block_size)
        self._hash_memo = (raw, keys)
        return keys

    def prefix_match(self, tokens) -> int:
        """Tokens of ``tokens``'s prefix this replica's cache already holds
        (cluster routers probe this for cache-affinity placement)."""
        if not self.prefix_cache:
            return 0
        return self.alloc.match_prefix(self._prompt_keys(tokens)) \
            * self.block_size

    def cache_hit_rate(self) -> float:
        s = self.cache_stats
        total = s["hit_tokens"] + s["miss_tokens"]
        return s["hit_tokens"] / total if total else 0.0

    def _on_pruned(self, req: Request) -> None:
        """Batcher pruned a dead waiting request: free its blocks."""
        self._release(req.rid)

    def _export_kv(self, req: Request) -> Optional[Tuple[np.ndarray, ...]]:
        # only chunk-capable (pure-attention) pools migrate prefix KV; the
        # hybrid never parks a partially-prefilled request.  Every leaf of
        # such a pool is layer-stacked blocks [L, num_blocks, bs, ...]
        if self._prefill_chunk is None:
            return None
        blocks = self.alloc.blocks_of(req.rid)
        need = self.alloc.blocks_for_tokens(req.prefilled)
        if len(blocks) < need:
            return None
        idx = jnp.asarray(blocks[:need], jnp.int32)
        return tuple(np.asarray(a[:, idx])
                     for a in jax.tree.leaves(self.cache))

    def _import_kv(self, req: Request, kv) -> bool:
        if self._prefill_chunk is None:
            return False
        leaves, tree = jax.tree.flatten(self.cache)
        nblk = kv[0].shape[1]
        if nblk > self.max_blocks or req.prompt_len + 1 > self.cap:
            # victim had a larger ring than ours: the prefix cannot resume
            # chunk-aligned here — recompute through the dense prefill
            return False
        if any(b.shape[2:] != a.shape[2:] for a, b in zip(leaves, kv)) or \
                not self.alloc.can_allocate(nblk * self.block_size,
                                            req.rid):
            return False                     # thief pool full: recompute
        self.alloc.ensure(req.rid, nblk * self.block_size)
        idx = jnp.asarray(self.alloc.blocks_of(req.rid)[:nblk], jnp.int32)
        self.cache = jax.tree.unflatten(tree, [
            a.at[:, idx].set(jnp.asarray(b)) for a, b in zip(leaves, kv)])
        return True

    def _copy_block(self, old: int, new: int) -> None:
        """Copy pool block ``old`` to ``new`` in every layer (a fork)."""
        self.cache = jax.tree.map(lambda a: a.at[:, new].set(a[:, old]),
                                  self.cache)
        self._table_dirty = True

    def _table_row(self, rid: int) -> np.ndarray:
        return self.alloc.table_row(rid, self.max_blocks)

    def _ensure_blocks(self, req: Request, tokens: int) -> bool:
        """Grow ``req``'s block table to cover ``tokens`` logical tokens,
        preempting less-urgent holders under pool pressure.  False when the
        pool cannot serve even after preemption (caller defers)."""
        tokens = min(tokens, self.cap)
        while True:
            try:
                self.alloc.ensure(req.rid, tokens)
                return True
            except PoolExhausted:
                if not self._preempt_for(req):
                    return False

    @staticmethod
    def _urgency(r: Request) -> tuple:
        """Total order: smaller = more urgent (rid breaks exact ties, so a
        strictly-less-urgent victim always exists among distinct requests
        unless the requester is the least urgent itself)."""
        return (r.priority, r.arrival, r.rid)

    def _preempt_for(self, req: Request) -> bool:
        """Free blocks by recompute-preempting a STRICTLY less urgent
        holder: waiting chunk-holders first (they only lose prefix
        recompute), then running requests (they re-enter the queue with
        their generated tokens folded into the prompt).  Never preempts
        ``req`` itself or anything more urgent — a bulk request cannot
        recompute-thrash an interactive one; if every holder outranks
        ``req``, it defers instead."""
        mine = self._urgency(req)
        holders = [r for r in self.batcher.waiting_requests()
                   if r.rid != req.rid and self.alloc.blocks_of(r.rid)
                   and self._urgency(r) > mine]
        if holders:
            victim = max(holders, key=self._urgency)   # least urgent first
            if self.batcher.preempt_waiting(victim):
                self._release(victim.rid)
                # keys died with the blocks; the victim lives on
                self._probe_prefix(victim, self.prompts[victim.rid])
                return True
        # chunk-holders planned later in THIS step: not in the storage yet,
        # so reclaim directly — their upcoming _run_prefill simply restarts
        # from chunk 0
        planned = [r for r in self._pending_prefill
                   if r.rid != req.rid and self.alloc.blocks_of(r.rid)
                   and self._urgency(r) > mine]
        if planned:
            victim = max(planned, key=self._urgency)
            victim.prefilled = 0
            self._release(victim.rid)
            self._probe_prefix(victim, self.prompts[victim.rid])
            self.batcher.metrics["preempted"] += 1
            return True
        actives = [r for r in self.slot_req
                   if r is not None and r.rid != req.rid
                   and self._urgency(r) > mine]
        if actives:
            victim = max(actives, key=self._urgency)
            self._preempt_running(victim)
            return True
        return False

    def _preempt_running(self, req: Request) -> None:
        """Recompute preemption of a decoding request: fold its generated
        tokens into the prompt, drop its KV, requeue it."""
        self._clear_slot(req)
        out = self.outputs.get(req.rid, [])
        if out:
            self.prompts[req.rid] = np.concatenate(
                [self.prompts[req.rid], np.asarray(out, np.int32)])
            req.prompt_len = len(self.prompts[req.rid])
        self._release(req.rid)
        # the folded prompt has new block keys — and if this request's own
        # prefix was published, its re-prefill will adopt it right back
        self._probe_prefix(req, self.prompts[req.rid])
        self.batcher.preempt(req)

    def _cow_for_write(self, req: Request, slot: int) -> bool:
        """Decode is about to write at ``slot``'s ring position.  When that
        lands in a block shared with another table (ring wrap back into an
        adopted prefix — sliding-window models do this routinely) the block
        is copy-on-write forked and its pool rows duplicated first; an
        exclusively-held published block is just unpublished.  False when a
        fork is needed but the pool is starved even after preemption."""
        j = (int(self.slot_pos[slot]) % self.cap) // self.block_size
        while True:
            try:
                fork = self.alloc.prepare_write(req.rid, j)
                break
            except PoolExhausted:
                if not self._preempt_for(req):
                    return False
        if fork is not None:
            self._copy_block(*fork)
        return True

    # -- speculative decoding primitives --------------------------------------
    def _spec_reserve(self, req: Request, slot: int, k: int) -> bool:
        """Reserve KV for one speculation round of ``slot``: blocks to
        cover positions ``[0, pos + k + 1)`` plus COW forks of every block
        the verify write range ``[pos, pos + k]`` touches — so a rejected
        draft can never land in a published/shared prefix block.  Strictly
        opportunistic: NO preemption; on pool exhaustion the growth is
        rolled back (``truncate``) and the round is shed."""
        if not self.paged:
            return False
        pos = int(self.slot_pos[slot])
        if pos + k + 1 > self.cap:
            return False                 # verify kernel's no-wrap contract
        before = self.alloc.allocated_tokens(req.rid)
        try:
            self.alloc.ensure(req.rid, pos + k + 1)
        except PoolExhausted:
            return False
        bs = self.block_size
        for j in range(pos // bs, (pos + k) // bs + 1):
            try:
                fork = self.alloc.prepare_write(req.rid, j)
            except PoolExhausted:
                self.alloc.truncate(req.rid, max(pos + 1, before))
                return False
            if fork is not None:
                self._copy_block(*fork)
        return True

    def _apply_accepted(self, slot: int, accepted: List[int]
                        ) -> Tuple[int, bool]:
        """Commit a verify round's accepted tokens to ``slot`` exactly as
        sequential decode steps would (EOS / budget checked per token), then
        roll the block table back to the committed length — rejected draft
        blocks return to the pool, published prefix blocks are untouched
        (acceptance only ever extends past the prompt).  Returns
        ``(tokens_applied, finished)``."""
        req = self.slot_req[slot]
        applied = 0
        finished = False
        for tok in accepted:
            self.outputs[req.rid].append(tok)
            applied += 1
            self.batcher.complete_decode([req])
            if (self.eos is not None and tok == self.eos) or \
                    req.generated >= req.max_new_tokens:
                finished = True
                break
        self.slot_pos[slot] += applied
        self.last_token[slot, 0] = accepted[applied - 1]
        if finished:
            req.state = RequestState.DONE
            req.finished_at = time.monotonic()
            self._clear_slot(req)
            self._release(req.rid)
        else:
            # stale KV past this point stays in the *kept* tail block but is
            # overwritten before any mask exposes it; whole stale blocks are
            # returned to the pool
            self.alloc.truncate(req.rid, int(self.slot_pos[slot]))
        return applied, finished

    @property
    def spec_stats(self) -> Dict[str, Any]:
        """Speculation counters (also surfaced via cluster telemetry)."""
        m = self.batcher.metrics
        drafted = m.get("spec_drafted", 0)
        accepted = m.get("spec_accepted", 0)
        return {
            "enabled": self.speculator is not None,
            "rounds": m.get("spec_rounds", 0),
            "drafted": drafted,
            "accepted": accepted,
            "wasted": m.get("spec_wasted", 0),
            "shed": m.get("spec_shed", 0),
            "merged_drafts": m.get("spec_merged_drafts", 0),
            "verify_calls": m.get("spec_verify_calls", 0),
            "warms": m.get("spec_warms", 0),
            "acceptance_rate": accepted / drafted if drafted else 0.0,
        }

    # -- engine loop ----------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def _clear_slot(self, req: Request) -> None:
        for i, r in enumerate(self.slot_req):
            if r is req:
                self.slot_req[i] = None
                if self.paged:
                    self.table[i, :] = SINK_BLOCK
                    self._table_dirty = True
                if self.speculator is not None:
                    # in-flight speculation dies with the slot: a stolen /
                    # preempted request resumes non-speculatively elsewhere
                    self.speculator.on_clear(i)

    def _run(self, span: TraceAnnotation, program, *args):
        """Call one of the engine's programs inside ``span``.  A call during
        which JAX compiled or loaded a program counts once in
        ``metrics["compiles"]`` and marks its span ``compiled=1``."""
        seen = _compiled()
        out = program(*args)
        self._note_compiles(span, seen)
        return out

    def _note_compiles(self, span: TraceAnnotation, seen: int) -> None:
        if _compiled() != seen:
            self.batcher.metrics["compiles"] += 1
            span.set_metadata(compiled=1)

    def _insert_contiguous(self, span: TraceAnnotation, slot: int,
                           cache_one) -> None:
        if self._insert is not None:
            # per-leaf batch axes (hybrid: KV axis 1, Mamba states axis 2)
            self.cache = self._run(span, self._insert, self.cache, cache_one,
                                   slot)
            return
        ax = self.batch_axis

        def put(full, one):
            idx = [slice(None)] * full.ndim
            idx[ax] = slice(slot, slot + 1)
            return full.at[tuple(idx)].set(one.astype(full.dtype))

        self.cache = jax.tree.map(put, self.cache, cache_one)

    def _take_slot(self, slot: int, req: Request, last_tok: int,
                   pos: int) -> None:
        self.slot_req[slot] = req
        self.slot_pos[slot] = pos
        self.last_token[slot, 0] = last_tok
        if self.paged:
            self.table[slot] = self._table_row(req.rid)
            self._table_dirty = True

    def _requeue(self, req: Request) -> bool:
        """Back to the waiting storage (lost slot / pool full); progress —
        prefilled chunks and their blocks — is kept."""
        req.state = RequestState.WAITING
        self.batcher.submit(req)
        return False

    def _adopt_cached_prefix(self, req: Request) -> None:
        """Start a cold prefill by adopting the longest published chain of
        the prompt's full blocks (capped one token short of the prompt — the
        final token must be prefilled to produce the first logits)."""
        rid = req.rid
        if not (self.prefix_cache and req.prefilled == 0
                and self.batcher.chunk_eligible(req)
                and not self.alloc.blocks_of(rid)):
            return
        adopted = self.alloc.adopt_prefix(rid, self._adoptable_keys(req))
        # actual adoption is the truth — a probe-time estimate whose chain
        # was evicted in the meantime must not keep under-pricing the
        # request to the cache-aware strategies
        req.prefilled = adopted * self.block_size
        req.cached_prefix = req.prefilled
        if rid in self._stat_seen:
            return                 # requeued retry: already counted
        self._stat_seen.add(rid)
        if adopted:
            self.cache_stats["hit_tokens"] += req.prefilled
            self.cache_stats["hit_requests"] += 1
        self.cache_stats["lookup_requests"] += 1
        self.cache_stats["miss_tokens"] += req.prompt_len - req.prefilled

    def _run_prefill(self, req: Request, chunk: int) -> bool:
        """Execute one planned prefill chunk.  Returns False when the
        request had to be requeued (no slot / no memory)."""
        rid = req.rid
        self._adopt_cached_prefix(req)
        chunk = min(chunk, req.remaining_prefill)
        whole = req.prefilled == 0 and chunk == req.prompt_len
        chunked = (self._prefill_chunk is not None
                   and self.batcher.chunk_eligible(req)
                   and not (whole and self.batcher.prefill_chunk is None))
        if not chunked:
            # whole-prompt (ring-aligning) dense prefill path
            chunk = req.remaining_prefill
        final = not chunked or req.prefilled + chunk >= req.prompt_len
        slot = None
        if final:
            slot = self._free_slot()
            if slot is None:
                return self._requeue(req)          # lost its slot
        if self.paged:
            need = req.prefilled + chunk if chunked else req.prompt_len
            if not self._ensure_blocks(req, need):
                return self._requeue(req)          # pool full; retry later
        # the dense path prefills the whole prompt from position 0
        start = req.prefilled if chunked else 0
        with TraceAnnotation("serve.prefill", rid=rid, start=start,
                             tokens=chunk if chunked else req.prompt_len
                             ) as span:
            if chunked:
                toks = self.prompts[rid][start:start + chunk]
                row = jnp.asarray(self._table_row(rid))
                logits, self.cache = self._run(
                    span, self._prefill_chunk, self.params,
                    {"tokens": jnp.asarray(toks[None, :])}, self.cache, row,
                    jnp.int32(start))
            else:
                toks = self.prompts[rid][None, :]
                logits, cache_one = self._run(
                    span, self._prefill, self.params,
                    {"tokens": jnp.asarray(toks)}, self.s_max)
                if self.paged:
                    # scatter the dense per-request cache into its blocks
                    row = jnp.asarray(self._table_row(rid))
                    self.cache = self._run(span, self._insert_prefill,
                                           self.cache, cache_one, row, slot)
                else:
                    self._insert_contiguous(span, slot, cache_one)
            done = self.batcher.complete_prefill_chunk(req, chunk)
            if not done:
                return True
            if self.prefix_cache and self.batcher.chunk_eligible(req):
                # every full prompt block is now written: publish the chain
                # so later prompts sharing the prefix adopt instead of
                # recompute (ring-wrapping prompts are excluded — their
                # block content is not the logical prefix)
                self.alloc.publish_prefix(rid, self._keys.get(rid, []))
            nxt = int(jnp.argmax(logits[0, -1]))
            self.outputs[rid].append(nxt)
            if req.first_token_at is None:
                req.first_token_at = self.batcher.now()
            req.generated += 1
            if (self.eos is not None and nxt == self.eos) or \
                    req.generated >= req.max_new_tokens:
                # single-token request (spawn-to-call shape): finished at
                # prefill — never takes a decode slot, cannot be preempted
                # into generating past its budget
                req.state = RequestState.DONE
                req.finished_at = time.monotonic()
                self.batcher.finish_running(req)
                self._release(rid)
                return True
            self._take_slot(slot, req, nxt, req.prompt_len)
        return True

    def step(self) -> int:
        """One engine step: evict, admit+prefill (possibly chunked),
        decode.  Returns the number of active slots stepped."""
        with StepTraceAnnotation("serve.step",
                                 step_num=self.batcher.metrics["steps"],
                                 device=self._device_id):
            return self._step()

    def _step(self) -> int:
        with TraceAnnotation("serve.plan"):
            plan: BatchPlan = self.batcher.plan_step()
            for req in plan.evicted:
                self._clear_slot(req)
                self._release(req.rid)
        self._pending_prefill = list(plan.prefill)
        for req in plan.prefill:
            self._pending_prefill.remove(req)
            self._run_prefill(req, plan.prefill_chunks.get(
                req.rid, req.remaining_prefill))
        # speculation round first: handled slots emit their tokens through
        # draft/verify and skip plain decode this step
        handled: set = set()
        if self.speculator is not None:
            with TraceAnnotation("serve.speculate") as span:
                seen = _compiled()
                handled = self.speculator.round(self)
                self._note_compiles(span, seen)
        # decode every occupied slot at its OWN position (attention_decode
        # takes per-sequence positions — continuous batching mixes depths)
        active = [i for i, r in enumerate(self.slot_req)
                  if r is not None and i not in handled]
        if self.paged and active:
            with TraceAnnotation("serve.blocks"):
                # the next write position may cross into a new block
                for i in list(active):
                    req = self.slot_req[i]
                    if req is None:
                        continue      # preempted by an earlier iteration
                    if not self._ensure_blocks(
                            req, int(self.slot_pos[i]) % self.cap + 1):
                        self._preempt_running(req)  # pool starved: recompute
                    elif self.prefix_cache and \
                            not self._cow_for_write(req, i):
                        self._preempt_running(req)  # fork, pool starved
                active = [i for i, r in enumerate(self.slot_req)
                          if r is not None and i not in handled]
                # refresh + re-upload the table only when something moved
                # (slot churn or block alloc/free); steady-state decode
                # reuses the cached device array
                if active and (self._table_dirty or
                               self._alloc_seen != self.alloc.version):
                    for i in active:
                        self.table[i] = self._table_row(
                            self.slot_req[i].rid)
                    self._table_dev = jnp.asarray(self.table)
                    self._alloc_seen = self.alloc.version
                    self._table_dirty = False
        if not active:
            return len(handled)
        # live_tokens: the positions the rows attend over, the sum the
        # attention's bytes and FLOPs are linear in; kv_tiles: the KV tiles
        # the flash decode kernel fetches for them
        live = np.minimum(self.slot_pos[active] + 1, self.cap)
        with TraceAnnotation("serve.decode", rows=len(active),
                             live_tokens=int(live.sum()),
                             kv_tiles=int((-(-live // self.kv_tile)).sum())
                             ) as span:
            pos_vec = jnp.asarray(self.slot_pos, jnp.int32)
            # a copy: on the CPU ``jnp.asarray`` would alias the buffer the
            # commit below writes into
            last = jnp.array(self.last_token)
            if self.paged:
                logits, self.cache = self._run(
                    span, self._decode, self.params, last, self.cache,
                    self._table_dev, pos_vec)
            else:
                logits, self.cache = self._run(
                    span, self._decode, self.params, last, self.cache,
                    pos_vec)
            nxt = jnp.argmax(logits[:, -1], axis=-1)
        with TraceAnnotation("serve.wait"):
            nxt.block_until_ready()
        with TraceAnnotation("serve.commit", rows=len(active), reads=1):
            # the step's tokens in one transfer; the rest is host work
            toks = np.asarray(jax.device_get(nxt))
            self.batcher.metrics["decode_host_reads"] += 1
            self.last_token[active, 0] = toks[active]
            self.slot_pos[active] += 1
            reqs = [self.slot_req[i] for i in active]
            self.batcher.complete_decode(reqs)
            for i, req in zip(active, reqs):
                tok = int(toks[i])
                self.outputs[req.rid].append(tok)
                if (self.eos is not None and tok == self.eos) or \
                        req.generated >= req.max_new_tokens:
                    req.state = RequestState.DONE
                    req.finished_at = time.monotonic()
                    self._clear_slot(req)
                    self._release(req.rid)
        return len(active) + len(handled)

    def run_until_drained(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        for _ in range(max_steps):
            self.step()
            busy = any(r is not None for r in self.slot_req)
            if not busy and self.batcher.waiting_count == 0 \
                    and not self.batcher.running:
                break
        return self.outputs
