"""Time under the step program's sampling scopes (``sample``: the logit
processors, the draw, the finiteness flag; the architecture's shapes
file lists them) over device busy time."""
from harness import scopes


def read(ctx):
    evs = scopes.scoped_events(ctx)
    if not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    by = scopes.by_class(evs, ctx["cfg"], ctx["arch"])
    ns = sum(by.get(k, 0) for k in ctx["arch"].SAMPLE_SCOPES)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
