"""Time under the state-space layers' scopes (their four projections,
the convolution and the selective scan: the architecture's shapes file
lists them as ``SSM_SCOPES``) over device busy time.  Nothing to read
where the architecture has no such layers or the program names no such
scopes."""
from harness import scopes


def read(ctx):
    names = getattr(ctx["arch"], "SSM_SCOPES", ())
    evs = scopes.scoped_events(ctx)
    if not names or not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    by = scopes.by_class(evs, ctx["cfg"], ctx["arch"])
    ns = sum(by.get(k, 0) for k in names)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
