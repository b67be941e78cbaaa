"""Kernels: the flash-decode kernel's share of its roofline.  The least
time the chip could take for the window's decode attention is the larger
of its bytes over peak HBM bandwidth and its FLOPs over peak FLOP/s (the
bytes bound it: a query row reads ``ctx * kvH * hd`` keys and values for
``4 * ctx * H * hd`` FLOPs).  Divided by the summed device time of the
kernel's events in the trace."""
from bench import flops

#: the kernel's operations in the device trace carry this in their label
#: (read by hand from a trace of the decode step on the chip)
KERNEL_EVENT = "flash_attention_pallas"


def read(ctx):
    if not ctx.peaks:
        return None
    tr = ctx.trace
    if tr is None:
        return None
    rows = [c for _, ctxs in ctx.in_window(ctx.decode_calls) for c in ctxs]
    t = tr.op_time_s(lambda label: KERNEL_EVENT in label, tr.window())
    if not rows or t <= 0:
        return None
    need = max(flops.decode_attention_bytes(ctx.dims, rows)
               / ctx.peaks["hbm_bytes_per_s"],
               flops.decode_attention_flops(ctx.dims, rows)
               / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * need / t
