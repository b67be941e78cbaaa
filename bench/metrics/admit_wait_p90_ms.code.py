"""Scheduler: the program's own stamps, from a request's arrival
(``Request.arrival``, when ``submit`` made it) to the first plan that took
it into prefill (``Request.admitted_at``), p90 over the requests due in
the window.  A program that does not stamp admission gives nothing."""
from bench.harness import percentile


def read(ctx):
    waits = []
    for r in ctx.requests:
        at = getattr(r.req, "admitted_at", None)
        if r.kind == "window" and at is not None:
            waits.append(at - r.req.arrival)
    if not waits:
        return None
    return percentile(waits, 90) * 1e3
