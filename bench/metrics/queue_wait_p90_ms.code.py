"""Scheduler: due time until the request first appears in a plan's
prefill list (``batcher.plan_step``, wrapped on the engine instance), p90
over the requests due in the window."""
from bench.harness import percentile


def read(ctx):
    waits = [r.first_plan - r.due for r in ctx.requests
             if r.kind == "window" and r.first_plan is not None]
    if not waits:
        return None
    return percentile(waits, 90) * 1e3
