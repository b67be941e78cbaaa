"""Model: device time of the decode program per run, over the traced
window (the program the harness's ``bench.decode`` calls launch, found by
``Trace.assign``)."""


def read(ctx):
    tr, prog = ctx.trace, ctx.programs.get("decode")
    if tr is None or prog is None:
        return None
    t = tr.program_time_s(prog)
    return t / tr.runs(prog) * 1e3 if t > 0 else None
