"""Kernels: the MLA decode kernel's share of its roofline.  The least time
the chip could take for the window's absorbed latent-attention decode is
the larger of its bytes over peak HBM bandwidth and its FLOPs over peak
FLOP/s (``bench/mla_flops.py``: live latents ``ctx x 576 x 2`` bytes per
row and layer plus query and output; ``2 x ctx x 16 x (576 + 512)``
FLOPs).  Divided by the summed device time of the kernel's events inside
the decode program."""
from bench import mla_flops
from bench.program_ops import op_time_in_program_s

#: the kernel's operations in the device trace carry this in their label
#: (read from the compiled decode program's HLO and a chip trace)
KERNEL_EVENT = "mla_decode_pallas"


def read(ctx):
    if not ctx.peaks:
        return None
    tr, prog = ctx.trace, ctx.programs.get("decode")
    if tr is None or prog is None:
        return None
    rows = [c for _, ctxs in ctx.in_window(ctx.decode_calls) for c in ctxs]
    t = op_time_in_program_s(tr, prog, lambda label: KERNEL_EVENT in label,
                             tr.window())
    if not rows or t <= 0:
        return None
    m = mla_flops.MLADims.from_config(ctx.hf)
    need = max(mla_flops.mla_decode_bytes(m, rows)
               / ctx.peaks["hbm_bytes_per_s"],
               mla_flops.mla_decode_flops(m, rows)
               / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * need / t
