"""Model: device time of the prefill program (the one the harness's
``bench.prefill`` calls launch, found by ``Trace.assign``) per 1000 prompt
tokens prefilled in the traced window."""


def read(ctx):
    tr, prog = ctx.trace, ctx.programs.get("prefill")
    chunks = ctx.prefill_chunks()
    if tr is None or prog is None or not chunks:
        return None
    t = tr.program_time_s(prog)
    toks = sum(n for _, n in chunks)
    return t * 1e3 / (toks / 1000.0) if t > 0 else None
