"""TPU Pallas kernels for the serving hot paths.

Five packages, one layout each: ``kernel.py`` holds the raw grid kernel
(exported as ``<name>_pallas``), ``ops.py`` the public jitted wrapper
(exported as ``<name>``, re-exported here), ``ref.py`` the pure-jnp oracle
the tests sweep against.  ``compat.py`` papers over jax API drift
(CompilerParams naming, interpret-mode auto-selection); every kernel routes
through it.
"""
from .flash_attention.ops import flash_attention
from .mla_decode.ops import mla_decode
from .moe_gmm.ops import grouped_swiglu
from .prefix_scan.ops import prefix_scan
from .wkv6.ops import wkv6

__all__ = ["flash_attention", "grouped_swiglu", "mla_decode", "prefix_scan",
           "wkv6"]
