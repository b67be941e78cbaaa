"""Compile the serving path's Pallas kernels, and one full-width decode step,
for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed alongside JAX, compiles
for a chip that is described and not attached, and refuses what the chip
would refuse (block shapes it cannot tile, primitives Mosaic cannot lower,
a program that does not fit the device).  Interpret-mode tests cannot see
any of that.  The topology is described inside a fixture, never at import
time: only one process at a time may load the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.moe_gmm.ops import grouped_swiglu
from repro.kernels.prefix_scan.ops import prefix_scan
from repro.kernels.wkv6.ops import wkv6

#: v5e high-bandwidth memory per chip (Google Cloud, "TPU v5e")
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def spec(one_chip):
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


QWEN2 = get_config("qwen2-1.5b")


@pytest.mark.parametrize("s", [256, 1024])
def test_flash_prefill_compiles(spec, s):
    h, kvh, d = QWEN2.num_heads, QWEN2.num_kv_heads, QWEN2.head_dim
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                             interpret=False),
             spec((1, s, h, d)), spec((1, s, kvh, d)), spec((1, s, kvh, d)))


def test_flash_decode_compiles(spec):
    """Batch > 1 with per-sequence valid counts: the shape decode uses."""
    b, t = 8, 2048
    h, kvh, d = QWEN2.num_heads, QWEN2.num_kv_heads, QWEN2.head_dim
    _compile(lambda q, k, v, n: flash_attention(q, k, v, n, causal=False,
                                                interpret=False),
             spec((b, 1, h, d)), spec((b, t, kvh, d)), spec((b, t, kvh, d)),
             spec((b,), jnp.int32))


@pytest.mark.parametrize("t", [64, 128])
def test_wkv6_compiles(spec, t):
    cfg = get_config("rwkv6-3b")
    h, n = cfg.d_model // cfg.rwkv_head_size, cfg.rwkv_head_size
    io = spec((1, t, h, n))
    _compile(lambda r, k, v, w, u, s0: wkv6(r, k, v, w, u, s0,
                                            interpret=False),
             io, io, io, spec((1, t, h, n), jnp.float32),
             spec((h, n)), spec((1, h, n, n), jnp.float32))


def test_moe_gmm_compiles(spec):
    cfg = get_config("mixtral-8x22b")
    e, c, d, f = cfg.num_experts, 128, cfg.d_model, cfg.resolved_moe_d_ff
    _compile(lambda x, g, u, dn: grouped_swiglu(x, g, u, dn,
                                                interpret=False),
             spec((e, c, d)), spec((e, d, f)), spec((e, d, f)),
             spec((e, f, d)))


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32, jnp.bfloat16])
def test_prefix_scan_compiles(spec, dtype):
    _compile(lambda x: prefix_scan(x, interpret=False),
             spec((3, 5000), dtype))


def test_qwen2_paged_decode_step_compiles(spec, one_chip, monkeypatch):
    """One full-width qwen2-1.5b paged decode step with kernels on, at the
    chip smoke's serving shape: B=8, 2048-token rings of 16-token blocks."""
    from repro.models import build_model
    # the kernels pick compiled-vs-interpret from the default backend,
    # which is the CPU here: steer them to the chip this compile is for
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    model = build_model(QWEN2.replace(use_flash=True))
    b, s_max, bs = 8, 2048, 16
    nblk = b * s_max // bs + 1

    def on_chip(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(b, nblk, bs)))
    compiled = _compile(model.decode_step_paged, params,
                        spec((b, 1), jnp.int32), cache,
                        spec((b, s_max // bs), jnp.int32),
                        spec((b,), jnp.int32))
    jax.clear_caches()
    mem = compiled.memory_analysis()
    # the output pool is a fresh buffer: arguments, output and temporaries
    # are all live while the step runs
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert live < V5E_HBM_BYTES, live
