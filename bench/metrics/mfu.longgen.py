"""Model, whole step: model FLOPs of every token the window processed
(decode rows with their logits, prefill chunks) over the window's seconds
times the chip's peak bf16 FLOP/s."""
from bench import flops


def read(ctx):
    if not ctx.peaks:
        return None
    m = ctx.dims
    f = sum(flops.token_flops(m, c, logits=True)
            for _, ctxs in ctx.in_window(ctx.decode_calls) for c in ctxs)
    chunks = ctx.prefill_chunks()
    if chunks is None:
        return None
    f += sum(flops.prefill_flops(m, start, n) for start, n in chunks)
    if f <= 0:
        return None
    secs = ctx.window[1] - ctx.window[0]
    return 100.0 * f / secs / ctx.peaks["bf16_flops_per_s"]
