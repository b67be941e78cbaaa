"""Backend selection and compiler params shared by the kernel packages.

The kernels are written for the TPU Mosaic backend, and every wrapper
accepts ``interpret``.  ``resolve_interpret`` maps the default (``None``) to
"compiled on TPU, interpreter on CPU"; any other backend is an error rather
than a silent fall back to the interpreter, so a run that meant to use the
chip cannot quietly measure the Pallas interpreter instead.

All four kernel packages (``flash_attention``, ``moe_gmm``, ``prefix_scan``,
``wkv6``) route through this module; new kernels should too.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.experimental.pallas import tpu as pltpu

__all__ = ["compiler_params", "resolve_interpret", "has_tpu"]


def compiler_params(*, dimension_semantics=None, **kw) -> pltpu.CompilerParams:
    """TPU compiler params; ``dimension_semantics`` as a tuple."""
    if dimension_semantics is not None:
        kw["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kw)


def has_tpu() -> bool:
    """True when JAX's default backend is the TPU.  A backend that fails to
    initialise raises here instead of reading as "no TPU"."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` → compiled on TPU, interpreter mode on CPU, error elsewhere.

    Explicit ``True``/``False`` is honored as-is (tests force the
    interpreter; a compile rehearsal for a described TPU forces
    compilation)."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels have no path on backend "
                       f"{backend!r}: run on a TPU, or on the CPU in "
                       f"interpret mode")
