"""Time under the selection's scope (``attn_select``: each query's
``index_topk`` largest index scores found and made a mask, and whatever
lays the mask out for the attention; the architecture's shapes file
lists it as ``SELECT_SCOPES``) over device busy time.  Nothing to read
where the architecture selects nothing or the program names no such
scope."""
from harness import scopes


def read(ctx):
    names = getattr(ctx["arch"], "SELECT_SCOPES", ())
    evs = scopes.scoped_events(ctx)
    if not names or not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    by = scopes.by_class(evs, ctx["cfg"], ctx["arch"])
    ns = sum(by.get(k, 0) for k in names)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
