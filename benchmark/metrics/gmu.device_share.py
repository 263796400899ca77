"""Time under the gated memory units' scope (``GMU_SCOPES`` of the
architecture's shapes file: the unit's two projections and its gate)
over device busy time.  Nothing to read where the architecture has no
such layers or the program names no such scope."""
from harness import scopes


def read(ctx):
    names = getattr(ctx["arch"], "GMU_SCOPES", ())
    evs = scopes.scoped_events(ctx)
    if not names or not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    by = scopes.by_class(evs, ctx["cfg"], ctx["arch"])
    ns = sum(by.get(k, 0) for k in names)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
