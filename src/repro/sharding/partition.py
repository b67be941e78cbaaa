"""Parameter partitioning rules: param-tree path → PartitionSpec.

Rules are matched on the path *suffix* and specify the spec for the LAST n
dimensions; leading dims (the stacked layer axis, Jamba's superblock axis)
are replicated automatically.  Tensor-parallel axes go on ``model``; MoE
experts go on ``model`` when the expert count divides the axis (expert
parallelism), otherwise the expert FFN dim is sharded (tensor parallelism
inside each expert — the Mixtral-8-experts-on-16-chips case).  Any
non-divisible dim falls back to replication instead of failing, so one rule
table serves every architecture and mesh.

ZeRO-1 / FSDP: ``fsdp_axes`` additionally shards the largest replicated dim
of big leaves over the data axes — used for optimizer state (ZeRO-1) and,
for the trillion-parameter configs, the parameters themselves.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["spec_for_path", "param_specs", "param_shardings", "batch_spec"]

# (path-suffix regex, spec for trailing dims, right-aligned)
_RULES: Tuple[Tuple[str, Tuple], ...] = (
    (r"embed/table$", ("model", None)),
    (r"(wq|wk|wv)/w$", (None, "model")),
    (r"(wq|wk|wv)/b$", ("model",)),
    (r"wo/w$", ("model", None)),
    (r"(gate|up)/w$", (None, "model")),
    (r"down/w$", ("model", None)),
    (r"lm_head/w$", (None, "model")),
    (r"router/w$", (None, None)),
    (r"w_(gate|up)$", ("__expert__", None, None)),   # filled per-config
    (r"w_down$", ("__expert__", None, None)),
    (r"vis_proj/fc1/w$", (None, "model")),
    (r"vis_proj/fc2/w$", ("model", None)),
    (r"audio_proj/w$", (None, "model")),
    # rwkv6
    (r"tm/(wr|wk|wv|wg)/w$", (None, "model")),
    (r"tm/wo/w$", ("model", None)),
    (r"cm/wk/w$", (None, "model")),
    (r"cm/wv/w$", ("model", None)),
    (r"cm/wr/w$", (None, "model")),
    # mamba
    (r"in_proj/w$", (None, "model")),
    (r"conv_w$", (None, "model")),
    (r"conv_b$", ("model",)),
    (r"x_proj/w$", ("model", None)),
    (r"dt_proj/w$", (None, "model")),
    (r"dt_proj/b$", ("model",)),
    (r"a_log$", ("model", None)),
    (r"d_skip$", ("model",)),
    (r"out_proj/w$", ("model", None)),
)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def spec_for_path(path: str, shape: Sequence[int], mesh: Mesh,
                  expert_parallel: bool = True,
                  fsdp_axes: Optional[Tuple[str, ...]] = None,
                  fsdp_min_size: int = 1 << 20,
                  tensor_parallel: bool = True,
                  embed_replicated: bool = False) -> P:
    """Partition spec for one leaf.  ``tensor_parallel=False`` keeps
    weights unsharded on the model axis (pure-DP layout for small models
    where TP activation all-reduces dominate); fsdp_axes still applies."""
    rank = len(shape)
    spec = [None] * rank
    if embed_replicated and re.search(r"embed/table$", path):
        # replicate the token table: a vocab-sharded gather hits XLA SPMD's
        # replicate-then-reshard fallback (huge implicit collectives)
        return P(*spec)
    for pat, tail in (_RULES if tensor_parallel else ()):
        if re.search(pat, path):
            tail = list(tail)
            # expert weights: EP over `model` when divisible, else TP on the
            # expert-internal dim
            if tail and tail[0] == "__expert__":
                e_dim = rank - len(tail)
                if expert_parallel and shape[e_dim] % _axis_size(
                        mesh, "model") == 0:
                    tail[0] = "model"
                else:
                    tail[0] = None
                    # shard the wider of the two inner dims
                    inner = int(shape[-1] < shape[-2])  # 1 → dim -2 bigger
                    tail[-1 - inner] = "model"
            offset = rank - len(tail)
            for i, ax in enumerate(tail):
                if ax is not None and shape[offset + i] % _axis_size(
                        mesh, ax) == 0:
                    spec[offset + i] = ax
            break
    if fsdp_axes:
        size = 1
        for s in shape:
            size *= s
        if size >= fsdp_min_size:
            fs = _axis_size(mesh, tuple(fsdp_axes))
            # largest replicated dim divisible by the fsdp axes
            cands = [i for i in range(rank)
                     if spec[i] is None and shape[i] % fs == 0]
            if cands:
                i = max(cands, key=lambda j: shape[j])
                spec[i] = tuple(fsdp_axes) if len(fsdp_axes) > 1 \
                    else fsdp_axes[0]
    return P(*spec)


def _path_str(kp) -> str:
    parts = []
    for k in kp:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_specs(params, mesh: Mesh, expert_parallel: bool = True,
                fsdp_axes: Optional[Tuple[str, ...]] = None,
                fsdp_min_size: int = 1 << 20,
                tensor_parallel: bool = True,
                embed_replicated: bool = False):
    """PartitionSpec pytree matching ``params`` (works on ShapeDtypeStructs
    too)."""
    return jax.tree_util.tree_map_with_path(
        lambda kp, x: spec_for_path(_path_str(kp), x.shape, mesh,
                                    expert_parallel, fsdp_axes,
                                    fsdp_min_size, tensor_parallel,
                                    embed_replicated),
        params)


def param_shardings(params, mesh: Mesh, **kw):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh, **kw))


def batch_spec(mesh: Mesh) -> P:
    """Input batches shard their leading (batch) dim over all data axes."""
    axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    return P(axes if len(axes) > 1 else axes[0])
