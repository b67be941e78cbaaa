"""Scheduler: mean decode batch over the window's steps that decoded, as a
share of ``max_batch`` (``ServingEngine.step`` returns the active slots)."""


def read(ctx):
    active = [n for _, _, n in ctx.steps if n > 0]
    if not active:
        return None
    return 100.0 * sum(active) / len(active) / ctx.cell["max_batch"]
