"""Model: the program's own stamps, from the first plan that took a
request into prefill (``Request.admitted_at``) to its first token on the
host (``Request.first_token_at``, taken after the argmax that makes it),
p90 over the requests due in the window.  A program that does not stamp
admission gives nothing."""
from bench.harness import percentile


def read(ctx):
    spans = []
    for r in ctx.requests:
        at = getattr(r.req, "admitted_at", None)
        first = getattr(r.req, "first_token_at", None)
        if r.kind == "window" and at is not None and first is not None:
            spans.append(first - at)
    if not spans:
        return None
    return percentile(spans, 90) * 1e3
