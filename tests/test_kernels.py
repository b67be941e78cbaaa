"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # optional dep: deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.kernels.flash_attention.decode import decode_tile
from repro.kernels.flash_attention.ops import flash_attention, flash_decode
from repro.kernels.flash_attention.ref import mha_ref
from repro.kernels.mla_decode.ops import mla_decode
from repro.kernels.mla_decode.ref import mla_decode_ref
from repro.kernels.moe_gmm.ops import grouped_swiglu
from repro.kernels.moe_gmm.ref import grouped_swiglu_ref
from repro.kernels.prefix_scan.ops import prefix_scan
from repro.kernels.prefix_scan.ref import prefix_scan_ref
from repro.kernels.wkv6.ops import wkv6
from repro.kernels.wkv6.ref import wkv6_ref


# ---------------------------------------------------------------- prefix scan
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 64), (4, 1000), (2, 3, 130), (8, 8)])
def test_prefix_scan_shapes(shape, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), shape) * 8).astype(dtype)
    got = prefix_scan(x, block=64)
    want = prefix_scan_ref(x)
    tol = 0.5 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want, np.float64), atol=tol)


@given(st.integers(1, 5), st.integers(1, 700), st.integers(8, 128),
       st.integers(0, 99))
@settings(max_examples=20, deadline=None)
def test_prefix_scan_property(rows, n, block, seed):
    block = 1 << int(np.log2(block))
    x = jax.random.randint(jax.random.PRNGKey(seed), (rows, n), -50, 50)
    got = prefix_scan(x.astype(jnp.int32), block=block)
    want = jnp.cumsum(x, axis=-1)
    assert (got == want).all()


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("b,s,t,h,hkv,d,causal,window", [
    (2, 64, 64, 4, 2, 32, True, None),
    (1, 128, 128, 4, 4, 64, True, 48),
    (2, 96, 96, 8, 2, 32, True, None),
    (1, 32, 96, 4, 1, 32, False, None),
    (1, 64, 64, 2, 2, 128, True, None),
    # s != t causal (top-left convention, matching the ref oracle)
    (1, 32, 96, 4, 2, 32, True, None),
    (2, 64, 128, 4, 1, 32, True, 48),
    # partial final q and kv blocks (padding + kv_len masking)
    (2, 40, 100, 4, 2, 32, True, None),
    (1, 100, 100, 4, 4, 32, False, None),
    (1, 24, 72, 2, 2, 32, True, 16),
])
def test_flash_attention_vs_ref(b, s, t, h, hkv, d, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          bq=32, bk=32)
    ref = jnp.moveaxis(
        mha_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1), causal=causal, window=window), 1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 64, 4, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 64, 2, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 64, 2, 32), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, bq=32, bk=32)
    ref = jnp.moveaxis(
        mha_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1), causal=True), 1, 2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


def test_flash_attention_gqa_window_bf16():
    """Combined case: grouped queries + sliding window + bf16 inputs."""
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (2, 96, 8, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (2, 96, 2, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (2, 96, 2, 32), jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, window=40, bq=32, bk=32)
    ref = jnp.moveaxis(
        mha_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1), causal=True, window=40), 1, 2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.parametrize("window", [None, 24])
def test_flash_attention_q_offset_bottom_right(window):
    """q_offset = t - s gives the bottom-right causal alignment a chunked
    prefill over history needs: new row i sees absolute cols <= t-s+i."""
    b, s, t, h, hkv, d = 1, 32, 96, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window,
                          bq=32, bk=32, q_offset=t - s)
    ref = jnp.moveaxis(
        mha_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1), causal=True, window=window,
                q_offset=t - s), 1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


def test_flash_attention_kv_valid_decode():
    """The flash-decode path: one query row per sequence, non-causal,
    per-batch valid-kv counts (a shared cache at mixed depths)."""
    b, t, h, hkv, d = 3, 40, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    kv_valid = jnp.asarray([5, 17, 40], jnp.int32)
    got = flash_attention(q, k, v, kv_valid, causal=False, bq=32, bk=32)
    ref = jnp.moveaxis(
        mha_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1), causal=False, kv_valid=kv_valid),
        1, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)


# The decode kernel's tile is 512 tokens at these shapes: valid counts of
# 1, exactly one tile, one past it and the whole ring, a ring that is not a
# whole number of tiles, and rows that end inside the first tile (their
# later tiles are neither fetched nor computed).
@pytest.mark.parametrize("t,h,hkv,d,kv_valid,dtype,atol", [
    (1024, 12, 2, 128, [1, 512, 513, 1024], jnp.float32, 2e-5),  # qwen2
    (1024, 12, 2, 128, [1, 512, 513, 1024], jnp.bfloat16, 1e-2),
    (700, 4, 4, 64, [700, 3, 512, 513], jnp.float32, 2e-5),      # MHA
    (600, 8, 1, 128, [600, 1, 64, 513], jnp.float32, 2e-5),      # MQA
    (1024, 12, 2, 64, [3, 17, 100, 1024], jnp.float32, 2e-5),
    (1024, 16, 8, 128, [2, 511, 40, 9], jnp.bfloat16, 1e-2),
    (300, 6, 3, 128, [300, 1, 299, 150], jnp.bfloat16, 1e-2),    # odd kvH
    (512, 12, 2, 128, [512, 7, 300, 1], jnp.float16, 1e-2),
], ids=["group6", "group6-bf16", "mha-hd64-padded", "mqa-padded",
        "hd64-first-tile", "kv8-bf16-first-tile", "kv3-bf16", "group6-f16"])
def test_flash_decode_vs_ref(t, h, hkv, d, kv_valid, dtype, atol):
    b = len(kv_valid)
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, 1, h, d), dtype)
    k = jax.random.normal(ks[1], (b, t, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, t, hkv, d), dtype)
    kv_valid = jnp.asarray(kv_valid, jnp.int32)
    got = flash_decode(q, k, v, kv_valid)
    assert got.shape == q.shape and got.dtype == dtype
    ref = jnp.moveaxis(
        mha_ref(jnp.moveaxis(q, 2, 1), jnp.moveaxis(k, 2, 1),
                jnp.moveaxis(v, 2, 1), causal=False, kv_valid=kv_valid),
        1, 2)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("cap,width,itemsize,bk", [
    (4096, 256, 2, 512),        # qwen2-1.5b
    (4096, 1024, 2, 512),       # qwen3-8b: a 1 MiB tile
    (4096, 2048, 2, 256),
    (4096, 1024, 4, 256),
    (100, 256, 2, 128),         # short ring: cut to a power of two
    (5, 256, 2, 16),
])
def test_decode_tile(cap, width, itemsize, bk):
    assert decode_tile(cap, width, itemsize) == bk


# ----------------------------------------------------------------- moe gmm
# ----------------------------------------------------------- MLA decode
@pytest.mark.parametrize("b,t,h,w,rank,bk", [
    (3, 200, 16, 96, 64, 64),       # padded tail block, H = 16
    (2, 256, 4, 40, 32, 128),       # whole blocks
])
def test_mla_decode_vs_ref(b, t, h, w, rank, bk):
    """Per-sequence valid counts (1, part of a block, the whole view): the
    blocks past each count are skipped, and masked where partly valid."""
    k = jax.random.split(jax.random.PRNGKey(3), 2)
    q = jax.random.normal(k[0], (b, h, w))
    lat = jax.random.normal(k[1], (b, t, w))
    valid = jnp.asarray([1, t // 2 + 3, t][:b], jnp.int32)
    got = mla_decode(q, lat, valid, rank=rank, scale=0.2, bk=bk)
    want = mla_decode_ref(q, lat, valid, rank=rank, scale=0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("e,c,d,f", [(4, 64, 32, 64), (2, 100, 16, 48),
                                     (8, 16, 128, 256), (1, 8, 8, 8)])
def test_grouped_swiglu_vs_ref(e, c, d, f):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (e, c, d), jnp.float32)
    wg = jax.random.normal(ks[1], (e, d, f)) / np.sqrt(d)
    wu = jax.random.normal(ks[2], (e, d, f)) / np.sqrt(d)
    wd = jax.random.normal(ks[3], (e, f, d)) / np.sqrt(f)
    got = grouped_swiglu(x, wg, wu, wd, bc=32, bf=32)
    want = grouped_swiglu_ref(x, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=1e-4)


# -------------------------------------------------------------------- wkv6
@pytest.mark.parametrize("b,t,h,n,chunk", [
    (2, 32, 2, 16, 8), (1, 64, 4, 32, 16), (2, 48, 3, 8, 16),
    (1, 16, 1, 64, 4)])
def test_wkv6_vs_ref(b, t, h, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    r = jax.random.normal(ks[0], (b, t, h, n), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, n), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, n), jnp.float32)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    y, s = wkv6(r, k, v, w, u, chunk=chunk)
    yr, sr = wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), atol=1e-3)


def test_wkv6_initial_state_handoff():
    """Running [0, T/2) then feeding s_end back as s0 for [T/2, T) must
    equal the single full-sequence run (prefill → decode → re-prefill)."""
    b, t, h, n = 2, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    r, k, v = (jax.random.normal(ks[i], (b, t, h, n)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    y_full, s_full = wkv6(r, k, v, w, u, chunk=8)
    half = t // 2
    def cut(a, sl):
        return a[:, sl]
    y1, s1 = wkv6(cut(r, slice(0, half)), cut(k, slice(0, half)),
                  cut(v, slice(0, half)), cut(w, slice(0, half)), u, chunk=8)
    y2, s2 = wkv6(cut(r, slice(half, t)), cut(k, slice(half, t)),
                  cut(v, slice(half, t)), cut(w, slice(half, t)), u, s1,
                  chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), atol=1e-3)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-3)


def test_wkv6_kernel_matches_train_path():
    """Pallas kernel ≡ chunked associative-scan (the training path) ≡ the
    naive scan oracle."""
    from repro.models.ssm import _wkv_chunk
    b, t, h, n = 2, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    r, k, v = (jax.random.normal(ks[i], (b, t, h, n)) for i in range(3))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, n))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (h, n)) * 0.1
    y_kernel, s_kernel = wkv6(r, k, v, w, u, chunk=8)
    y_assoc, s_assoc = _wkv_chunk(r, k, v, w, u,
                                  jnp.zeros((b, h, n, n)))
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_assoc),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(s_kernel), np.asarray(s_assoc),
                               atol=1e-3)


# ------------------------------------------------------------ backend policy
@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_resolve_interpret_follows_backend(monkeypatch, backend, interpret):
    """The interpreter only on the CPU, compiled Mosaic on the TPU, and an
    error (never a silent interpreter fallback) anywhere else."""
    from repro.kernels import compat
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="gpu"):
            compat.resolve_interpret(None)
    else:
        assert compat.resolve_interpret(None) is interpret
    assert compat.resolve_interpret(True) is True
    assert compat.resolve_interpret(False) is False
    assert compat.has_tpu() is (backend == "tpu")
