"""Model: device time of the expert layer's grouped SwiGLU kernel (the
held routed experts and the shared experts) inside the decode program, per
run of the program, in ms.  The router's, dispatch's and combine's XLA
operations are left out: the trace's labels do not carry the ``moe`` named
scope (``bench/program_ops.py``)."""
from bench.program_ops import op_time_in_program_s

#: the kernel's operations in the device trace carry this in their label
KERNEL_EVENT = "grouped_swiglu_pallas"


def read(ctx):
    tr, prog = ctx.trace, ctx.programs.get("decode")
    if tr is None or prog is None:
        return None
    t = op_time_in_program_s(tr, prog, lambda label: KERNEL_EVENT in label)
    runs = tr.runs(prog)
    return t / runs * 1e3 if t > 0 and runs else None
