"""Compile the serving path's Pallas kernels, and one full-width decode step,
for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed alongside JAX, compiles
for a chip that is described and not attached, and refuses what the chip
would refuse (block shapes it cannot tile, primitives Mosaic cannot lower,
a program that does not fit the device).  Interpret-mode tests cannot see
any of that.  The topology is described inside a fixture, never at import
time: only one process at a time may load the TPU library.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mla_decode.ops import mla_decode
from repro.kernels.moe_gmm.ops import grouped_swiglu
from repro.kernels.prefix_scan.ops import prefix_scan
from repro.kernels.wkv6.ops import wkv6
from repro.serving.paged_kv import jit_pool_program

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
DEEPSEEK = get_config("deepseek-v2-lite")


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


def test_mla_decode_compiles(spec):
    """deepseek-v2-lite's absorbed decode: 16 heads over 576-value latents
    (512 of them the values), the longgen cell's 32 rows and 4096 ring."""
    cfg = get_config("deepseek-v2-lite")
    h, w, r = cfg.num_heads, cfg.mla_latent_width, cfg.kv_lora_rank
    _compile(lambda q, lat, n: mla_decode(q, lat, n, rank=r, scale=0.1,
                                          interpret=False),
             spec((32, h, w)), spec((32, 4096, w)), spec((32,), jnp.int32))


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


@pytest.fixture
def chip_kernels(monkeypatch):
    """The kernels pick compiled-vs-interpret from the default backend,
    which is the CPU here: steer them to the chip these compiles are for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _paged(spec, cfg, b: int, s_max: int, bs: int = 16):
    """(model with kernels on, its parameters, its pool) for ``b`` rows of
    ``s_max``-token rings of ``bs``-token blocks, as shapes on the chip."""
    from repro.models import build_model
    model = build_model(cfg.replace(use_flash=True))

    def on_chip(tree):
        return jax.tree.map(lambda a: spec(a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    pool = on_chip(jax.eval_shape(
        lambda: model.init_paged_cache(b, b * s_max // bs + 1, bs)))
    return model, params, pool


def _compile_pool_program(spec, cfg, program: str, b: int, s_max: int,
                          bs: int = 16, chunk: int = 512):
    """Compile the model's ``program`` the way the serving engine jits it
    (the pool donated): a decode step over ``b`` rows, or one
    ``chunk``-token prompt chunk.  Returns (compiled, pool)."""
    model, params, pool = _paged(spec, cfg, b, s_max, bs)
    if program == "decode_step_paged":
        args = (params, spec((b, 1), jnp.int32), pool,
                spec((b, s_max // bs), jnp.int32), spec((b,), jnp.int32))
    else:
        args = (params, {"tokens": spec((1, chunk), jnp.int32)}, pool,
                spec((s_max // bs,), jnp.int32), spec((), jnp.int32))
    compiled = jit_pool_program(getattr(model, program)).lower(
        *args).compile()
    return compiled, pool


def _live_bytes(compiled) -> int:
    """Device bytes live while the program runs: arguments, outputs and
    temporaries, with each output that reuses a donated argument's buffer
    counted once."""
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


_HLO_VALUE = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(([^)]*)\)")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def _pool_moves(text: str, pool) -> list:
    """The instructions of ``text`` that copy, slice out or write back a
    whole stacked pool or one layer of it: a ``copy`` or
    ``dynamic-slice`` (or a fusion named after one, or after
    ``dynamic-update-slice``) of such a shape, or a
    ``dynamic-update-slice`` whose update is a layer's size or more.  A
    smaller update writes rows where the pool lies."""
    shapes = set()
    for a in jax.tree.leaves(pool):
        shapes |= {a.shape, a.shape[1:], (1,) + a.shape[1:]}
    layer = min(math.prod(a.shape[1:]) for a in jax.tree.leaves(pool))
    values, moves = {}, []
    for line in text.splitlines():
        m = _HLO_VALUE.match(line)
        if not m:
            continue
        name, dims, op, operands = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        values[name] = shape
        if op == "dynamic-update-slice":
            update = values.get(operands.split(", ")[1].lstrip("%"))
            if update is None or math.prod(update) >= layer:
                moves.append(line.strip()[:160])
        elif shape in shapes and any(w in op or w in name for w in _MOVES):
            moves.append(line.strip()[:160])
    return moves


def test_qwen2_paged_decode_step_compiles(spec, chip_kernels):
    """One full-width qwen2-1.5b paged decode step with kernels on, at the
    chip smoke's serving shape: B=8, 2048-token rings of 16-token blocks."""
    compiled, _ = _compile_pool_program(spec, QWEN2, "decode_step_paged", 8,
                                        2048)
    assert "tpu_custom_call" in compiled.as_text()
    live = _live_bytes(compiled)
    assert live < V5E_HBM_BYTES, live


def test_deepseek_v2_lite_paged_decode_step_compiles(spec, chip_kernels):
    """One deepseek-v2-lite paged decode step with kernels on, at the
    longgen cell's serving shape: 32 rows, 4096-token rings of 16-token
    blocks over the latent pool, 8 held experts of 64."""
    compiled, _ = _compile_pool_program(spec, DEEPSEEK, "decode_step_paged",
                                        32, 4096)
    text = compiled.as_text()
    assert "mla_decode_pallas" in text and "grouped_swiglu_pallas" in text
    live = _live_bytes(compiled)
    assert live < V5E_HBM_BYTES, live


def test_qwen2_longgen_decode_step_runs_the_decode_kernel(spec,
                                                          chip_kernels):
    """One full-width qwen2-1.5b paged decode step at the longgen cell's
    shape (32 rows, 4096-token rings of 16-token blocks) runs the flash
    decode kernel under a name the roofline reader matches, hands it the
    gathered K/V without a relayout copy, and fits one chip."""
    b, s_max = 32, 4096
    compiled, _ = _compile_pool_program(spec, QWEN2, "decode_step_paged", b,
                                        s_max)
    text = compiled.as_text()
    kernel = "flash_attention_pallas_decode"
    # bench/metrics/flash_decode_roofline.longgen.py matches this substring
    assert "flash_attention_pallas" in kernel and kernel in text
    # the [B, cap*kvH, hd] view the kernel reads is only ever a bitcast
    view = f"bf16[{b},{s_max * QWEN2.num_kv_heads},{QWEN2.head_dim}]"
    made = [ln for ln in text.splitlines() if f"= {view}" in ln]
    assert made and all(" bitcast(" in ln for ln in made), made
    live = _live_bytes(compiled)
    assert live < V5E_HBM_BYTES, live


@pytest.mark.parametrize("program", ["decode_step_paged",
                                     "prefill_chunk_paged"])
@pytest.mark.parametrize("cfg", [QWEN2, DEEPSEEK], ids=lambda c: c.name)
def test_pool_programs_write_the_pool_in_place(spec, chip_kernels, cfg,
                                               program):
    """The longgen cells' decode step (32 rows, 4096-token rings of
    16-token blocks) and 512-token prompt chunk, jitted as the engine jits
    them: the output pool is the donated input's buffer, and no
    instruction copies the pool, slices a layer out of it or writes a
    layer back.  Temporaries hold a layer's gathered view (for the latent
    pool, also its relayout to the kernel's ``[B, cap, 576]``) and a
    chunk's attention scores, each at most one layer's pool in size: all
    of them stay below three layers' pools."""
    compiled, pool = _compile_pool_program(spec, cfg, program, 32, 4096)
    leaves = jax.tree.leaves(pool)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in leaves)
    layer_bytes = sum(a.size // a.shape[0] * a.dtype.itemsize
                      for a in leaves if a.shape[0] > 1)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes, (mem.alias_size_in_bytes,
                                                  pool_bytes)
    moves = _pool_moves(compiled.as_text(), pool)
    assert not moves, moves
    assert mem.temp_size_in_bytes < 3 * layer_bytes, (
        mem.temp_size_in_bytes, layer_bytes)
