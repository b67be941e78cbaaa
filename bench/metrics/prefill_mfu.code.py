"""Model, whole prefill call: model FLOPs of the window's prefill chunks
over the device time of the prefill program (``Trace.assign``), as a share
of the chip's peak bf16 FLOP/s."""
from bench import flops


def read(ctx):
    tr, prog = ctx.trace, ctx.programs.get("prefill")
    chunks = ctx.prefill_chunks()
    if not ctx.peaks or tr is None or prog is None or not chunks:
        return None
    f = sum(flops.prefill_flops(ctx.dims, start, n) for start, n in chunks)
    t = tr.program_time_s(prog)
    if f <= 0 or t <= 0:
        return None
    return 100.0 * f / t / ctx.peaks["bf16_flops_per_s"]
