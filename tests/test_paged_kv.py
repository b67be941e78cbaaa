"""Paged KV cache: allocator invariants (property tests, including the
refcounted copy-on-write prefix cache) + fragmented block-table decode
against the dense reference + shared-prefix decode bit-exactness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:
    from _hypothesis_fallback import given, settings, st

from repro.configs import get_config, scale_down
from repro.models import build_model
from repro.serving import ServingEngine
from repro.serving.paged_kv import (SINK_BLOCK, BlockAllocator,
                                    PoolExhausted, prefix_block_keys)

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------- allocator
def test_allocator_basics():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert a.total_blocks == 7 and a.free_tokens == 28
    new = a.ensure(1, 10)                 # ceil(10/4) = 3 blocks
    assert len(new) == 3 and SINK_BLOCK not in new
    assert a.allocated_tokens(1) == 12
    assert a.ensure(1, 12) == []          # already covered
    row = a.table_row(1, 7)
    assert list(row[:3]) == a.blocks_of(1)
    assert all(b == SINK_BLOCK for b in row[3:])
    a.check()
    assert a.free(1) == 3
    assert a.free_tokens == 28
    a.check()


def test_allocator_double_free_raises():
    a = BlockAllocator(num_blocks=4, block_size=2)
    a.ensure(7, 3)
    a.free(7)
    with pytest.raises(KeyError):
        a.free(7)
    assert a.release(7) == 0              # engine path: tolerant
    a.check()


def test_allocator_exhaustion_has_no_side_effects():
    a = BlockAllocator(num_blocks=4, block_size=2)   # 3 allocatable
    a.ensure(1, 4)                        # 2 blocks
    with pytest.raises(PoolExhausted):
        a.ensure(2, 6)                    # needs 3, only 1 free
    a.check()
    assert a.num_requests == 1            # rid 2 left no residue
    assert a.ensure(2, 2) and a.num_free == 0
    a.check()


@settings(max_examples=30)
@given(st.lists(st.integers(min_value=0, max_value=2 ** 20),
                min_size=1, max_size=80))
def test_allocator_never_leaks_under_random_ops(ops):
    """Random admit/extend/evict/migrate sequences across two pools (the
    cross-replica steal shape) preserve the no-leak / no-double-alloc
    invariants after every operation."""
    pools = [BlockAllocator(num_blocks=12, block_size=4),
             BlockAllocator(num_blocks=9, block_size=4)]
    live = [[], []]                        # rids per pool
    next_rid = 0
    for v in ops:
        which = (v >> 2) % 2
        a, mine = pools[which], live[which]
        op = v % 4
        try:
            if op == 0:                    # admit
                a.ensure(next_rid, (v >> 4) % 40 + 1)
                mine.append(next_rid)
                next_rid += 1
            elif op == 1 and mine:         # extend
                rid = mine[(v >> 4) % len(mine)]
                a.ensure(rid, a.allocated_tokens(rid) + (v >> 4) % 16 + 1)
            elif op == 2 and mine:         # evict
                rid = mine.pop((v >> 4) % len(mine))
                a.free(rid)
            elif op == 3 and mine:         # migrate to the other pool
                rid = mine[(v >> 4) % len(mine)]
                tokens = a.allocated_tokens(rid)
                other = pools[1 - which]
                other.ensure(rid, tokens)  # thief allocates first...
                a.free(rid)                # ...then the victim releases
                mine.remove(rid)
                live[1 - which].append(rid)
        except PoolExhausted:
            pass                           # admission control, not a bug
        for p in pools:
            p.check()
    for p, mine in zip(pools, live):
        for rid in list(mine):
            p.free(rid)
        p.check()
        assert p.num_free == p.total_blocks


# ----------------------------------------------- prefix cache / refcounts
def test_prefix_keys_are_chained():
    toks = np.arange(16, dtype=np.int32)
    a = prefix_block_keys(toks, 4)
    assert len(a) == 4
    # same block content at a different prefix position gets a new key
    b = prefix_block_keys(np.concatenate([toks[4:8], toks[4:8]]), 4)
    assert a[1] != b[0] and b[0] != b[1]
    # partial trailing block gets no key
    assert len(prefix_block_keys(toks[:7], 4)) == 1


def test_adopt_publish_share_and_release_to_lru():
    a = BlockAllocator(num_blocks=8, block_size=4)
    toks = np.arange(12, dtype=np.int32)
    keys = prefix_block_keys(toks, 4)
    a.ensure(1, 12)
    assert a.match_prefix(keys) == 0
    assert a.publish_prefix(1, keys) == 3
    a.check()
    assert a.match_prefix(keys) == 3
    # adoption: same physical blocks head the second table
    assert a.adopt_prefix(2, keys) == 3
    assert a.blocks_of(2) == a.blocks_of(1)
    a.check()
    # the sharer extends privately: the grown block is fresh, not aliased
    a.ensure(2, 16)
    assert a.blocks_of(2)[:3] == a.blocks_of(1)
    assert a.blocks_of(2)[3] not in a.blocks_of(1)
    # release one holder: blocks stay held (refcount), not cached
    a.free(1)
    assert a.num_cached == 0
    a.check()
    # release the last holder: published blocks join the cached LRU tail
    a.free(2)
    assert a.num_cached == 3 and a.cached_tokens == 12
    a.check()
    # still adoptable from the tail
    assert a.adopt_prefix(3, keys) == 3
    assert a.num_cached == 0
    a.free(3)
    a.check()


def test_pool_pressure_evicts_cached_tail_before_exhausting():
    a = BlockAllocator(num_blocks=8, block_size=4)   # 7 allocatable
    toks = np.arange(12, dtype=np.int32)
    keys = prefix_block_keys(toks, 4)
    a.ensure(1, 12)
    a.publish_prefix(1, keys)
    a.free(1)                                        # 3 cached, 4 free
    assert (a.num_free, a.num_cached) == (4, 3)
    assert a.can_allocate(7 * 4)                     # cached tail counts
    a.ensure(2, 24)                                  # 6 blocks: evicts 2
    assert a.cache_evictions == 2
    assert a.num_cached == 1
    a.check()
    # oldest evicted first: the chain head is gone, so no prefix matches
    assert a.match_prefix(keys) == 0
    with pytest.raises(PoolExhausted):
        a.ensure(3, 12)                              # needs 3, has 1+1
    a.check()
    a.free(2)
    a.check()


def test_prepare_write_forks_shared_and_unpublishes_exclusive():
    a = BlockAllocator(num_blocks=8, block_size=4)
    toks = np.arange(8, dtype=np.int32)
    keys = prefix_block_keys(toks, 4)
    a.ensure(1, 8)
    a.publish_prefix(1, keys)
    a.adopt_prefix(2, keys)
    shared = a.blocks_of(1)
    # write into a block shared by two tables: COW fork
    fork = a.prepare_write(2, 0)
    assert fork is not None
    old, new = fork
    assert old == shared[0] and new not in shared
    assert a.blocks_of(2)[0] == new and a.blocks_of(1) == shared
    assert a.cow_forks == 1
    a.check()
    # writer holds block 1 exclusively? no — still shared with rid 1
    assert a.prepare_write(2, 1) is not None
    a.check()
    a.release(2)
    # rid 1 now holds its published blocks exclusively: a write just
    # unpublishes (no copy — nobody else can be reading them)
    assert a.prepare_write(1, 0) is None
    assert a.match_prefix(keys) == 0                 # chain head unpublished
    a.check()
    a.release(1)
    a.check()


def test_adopt_requires_empty_table():
    a = BlockAllocator(num_blocks=8, block_size=4)
    keys = prefix_block_keys(np.arange(8, dtype=np.int32), 4)
    a.ensure(1, 8)
    a.publish_prefix(1, keys)
    a.ensure(2, 4)
    with pytest.raises(ValueError):
        a.adopt_prefix(2, keys)
    a.check()


@settings(max_examples=30)
@given(st.lists(st.integers(min_value=0, max_value=2 ** 24),
                min_size=1, max_size=120))
def test_refcounted_allocator_never_leaks_under_random_ops(ops):
    """Random admit/extend/publish/adopt/fork/free/evict/clear sequences
    preserve the refcounted no-leak invariant (held ∪ cached ∪ free
    partitions the pool; refcounts match table membership) after every
    operation."""
    a = BlockAllocator(num_blocks=10, block_size=4)
    # a small universe of shareable prefixes (chained keys, 1-3 blocks)
    prefixes = [prefix_block_keys(np.arange(n * 4, dtype=np.int32) + s, 4)
                for s, n in ((0, 1), (100, 2), (200, 3))]
    live: list = []
    next_rid = 0
    for v in ops:
        op = v % 6
        try:
            if op == 0:                    # admit cold
                rid, next_rid = next_rid, next_rid + 1
                live.append(rid)           # rid may end up empty: released
                a.ensure(rid, (v >> 4) % 24 + 1)
            elif op == 1:                  # admit by adoption
                keys = prefixes[(v >> 4) % len(prefixes)]
                rid, next_rid = next_rid, next_rid + 1
                live.append(rid)           # keeps adopted blocks owned even
                n = a.adopt_prefix(rid, keys)   # if the extend below fails
                a.ensure(rid, n * 4 + (v >> 6) % 8 + 1)
            elif op == 2 and live:         # extend
                rid = live[(v >> 4) % len(live)]
                a.ensure(rid, a.allocated_tokens(rid) + (v >> 6) % 8 + 1)
            elif op == 3 and live:         # publish under a prefix chain
                rid = live[(v >> 4) % len(live)]
                a.publish_prefix(rid, prefixes[(v >> 6) % len(prefixes)])
            elif op == 4 and live:         # COW write somewhere
                rid = live[(v >> 4) % len(live)]
                nblk = len(a.blocks_of(rid))
                if nblk:
                    a.prepare_write(rid, (v >> 6) % nblk)
            elif op == 5 and live:         # release (rid may hold nothing
                rid = live.pop((v >> 4) % len(live))   # if admission failed)
                a.release(rid)
        except PoolExhausted:
            pass                           # admission control, not a bug
        a.check()
    for rid in list(live):
        a.release(rid)                     # tolerant: rid may hold nothing
        a.check()
    a.clear_cache()
    a.check()
    assert a.num_free == a.total_blocks


def test_shared_prefix_decode_bit_exact_vs_private_copies():
    """Two requests sharing a cached prompt prefix (one physical copy,
    refcounted) must decode bit-identically (fp32) to the same requests
    each holding private blocks — and to the contiguous engine."""
    cfg = scale_down(get_config("qwen2-1.5b")).replace(
        dtype="float32", param_dtype="float32")
    m = build_model(cfg)
    params = m.init(KEY)
    rng = np.random.default_rng(31)
    sysp = rng.integers(0, cfg.vocab_size, 16)
    prompts = [np.concatenate([sysp, rng.integers(0, cfg.vocab_size, n)])
               for n in (5, 9)]
    kw = dict(max_batch=2, s_max=64, kv_mode="paged", block_size=8,
              prefill_chunk=8)

    def run(prefix_cache):
        eng = ServingEngine(m, params, prefix_cache=prefix_cache, **kw)
        first = eng.submit(prompts[0], 4)
        while first.state.name == "WAITING" or first.state.name == "PREFILL":
            eng.step()                     # publish the prefix before #2
        second = eng.submit(prompts[1], 4)
        outs = eng.run_until_drained()
        assert first.state.name == "DONE" and second.state.name == "DONE"
        eng.alloc.check()
        return [outs[first.rid], outs[second.rid]], eng

    private, _ = run(prefix_cache=False)
    shared, eng = run(prefix_cache=True)
    assert shared == private
    assert eng.cache_stats["hit_tokens"] == 16      # two full blocks adopted
    ref_eng = ServingEngine(m, params, max_batch=2, s_max=64,
                            kv_mode="contiguous")
    refs = [ref_eng.submit(p, 4) for p in prompts]
    ref_outs = ref_eng.run_until_drained()
    assert shared == [ref_outs[r.rid] for r in refs]


# ------------------------------------- fragmented-table decode vs dense
@pytest.mark.parametrize(
    "use_flash,c,window",
    [(False, 1, None), (True, 1, None), (False, 4, None), (True, 4, None),
     (False, 4, 32)],
    ids=["xla", "flash-decode", "xla-verify", "flash-verify",
         "xla-verify-window"])
def test_fragmented_block_table_decode_matches_dense(use_flash, c, window):
    """Two requests whose blocks interleave in the pool (worst-case
    fragmentation), at different depths in one batch: ``c`` tokens per row
    through the paged gather (decode at ``c == 1``, the verify shape at
    ``c == 4``) must reproduce ``c`` dense contiguous decode steps
    bit-for-bit (fp32) on one attention path.  Verify takes the masked XLA
    path, so against the flash kernel's decode it agrees to fp32 rounding.
    With ``window`` the sliding window is below the requested ``s_max``,
    so the ring is clamped to the window."""
    cfg = scale_down(get_config("qwen2-1.5b")).replace(
        dtype="float32", param_dtype="float32", use_flash=use_flash,
        sliding_window=window)
    m = build_model(cfg)
    params = m.init(KEY)
    bs, cap = 8, 32
    nblk = cap // bs
    lens = [17, 9]                         # mixed depths
    toks = [jax.random.randint(jax.random.PRNGKey(i), (1, n), 0,
                               cfg.vocab_size) for i, n in enumerate(lens)]

    # interleaved allocation -> fragmented, non-contiguous block tables
    alloc = BlockAllocator(num_blocks=2 * nblk + 1, block_size=bs)
    for tokens in range(bs, cap + 1, bs):
        for rid in (0, 1):
            if tokens <= ((lens[rid] + c - 1 + bs - 1) // bs) * bs:
                alloc.ensure(rid, min(tokens, lens[rid] + c - 1))
    tables = [alloc.blocks_of(r) for r in (0, 1)]
    assert tables[0] != sorted(tables[0]) or \
        any(abs(a - b) > 1 for a, b in zip(tables[0], tables[0][1:])), \
        f"expected fragmentation, got {tables}"

    pool = m.init_paged_cache(2, 2 * nblk + 1, bs)
    denses = []
    for rid, t in enumerate(toks):
        _, dense = m.prefill(params, {"tokens": t}, 2 * cap if window
                             else cap)
        denses.append(dense)
        row = jnp.asarray(alloc.table_row(rid, nblk))
        pool = m.insert_prefill_paged(pool, dense, row, rid)

    batch_cache = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=1),
                               denses[0], denses[1])
    tok = jnp.asarray([[3, 7, 11, 2], [5, 1, 9, 4]], jnp.int32)[:, :c]
    pos = jnp.asarray(lens, jnp.int32)
    refs = []
    for i in range(c):
        r, batch_cache = m.decode_step(params, tok[:, i:i + 1], batch_cache,
                                       pos + i)
        refs.append(r)
    ref = jnp.concatenate(refs, axis=1)
    table = jnp.asarray(np.stack([alloc.table_row(r, nblk)
                                  for r in (0, 1)]))
    step = m.decode_step_paged if c == 1 else m.verify_paged
    got, _ = step(params, tok, pool, table, pos)
    if use_flash and c > 1:
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5, rtol=0)
    else:
        assert jnp.array_equal(ref, got), \
            float(jnp.max(jnp.abs(ref - got)))


def test_chunked_prefill_paged_matches_dense_prefill():
    """Chunked prefill through the block table reproduces the dense
    whole-prompt prefill (numerics-gated: reduction widths differ)."""
    cfg = scale_down(get_config("qwen2-1.5b")).replace(
        dtype="float32", param_dtype="float32")
    m = build_model(cfg)
    params = m.init(KEY)
    n, cap, bs, chunk = 22, 32, 8, 8
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, n), 0,
                              cfg.vocab_size)
    lg_dense, _ = m.prefill(params, {"tokens": toks}, cap)
    alloc = BlockAllocator(num_blocks=cap // bs + 1, block_size=bs)
    pool = m.init_paged_cache(1, cap // bs + 1, bs)
    start = 0
    while start < n:
        c = min(chunk, n - start)
        alloc.ensure(0, start + c)
        row = jnp.asarray(alloc.table_row(0, cap // bs))
        lg, pool = m.prefill_chunk_paged(
            params, {"tokens": toks[:, start:start + c]}, pool, row,
            jnp.int32(start))
        start += c
    err = float(jnp.max(jnp.abs(lg_dense - lg)))
    assert err < 1e-4, err
