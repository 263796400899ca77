"""Time under the expert layers' scopes (the router, the sort of pairs by
expert, the grouped products of the routed experts, the weighted sum
back: the architecture's shapes file lists them as ``MOE_SCOPES``) over
device busy time.  The shared expert is a dense product and counts with
the matmuls.  Nothing to read where the architecture has no expert
layers or the program names no such scopes."""
from harness import scopes


def read(ctx):
    names = getattr(ctx["arch"], "MOE_SCOPES", ())
    evs = scopes.scoped_events(ctx)
    if not names or not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    by = scopes.by_class(evs, ctx["cfg"], ctx["arch"])
    ns = sum(by.get(k, 0) for k in names)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
