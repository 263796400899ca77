"""Time under the indexer's scope (``attn_index``: the index heads'
three projections, the score product over every key a query sees and
the sum over heads; the architecture's shapes file lists it as
``INDEX_SCOPES``) over device busy time.  Nothing to read where the
architecture has no indexer or the program names no such scope."""
from harness import scopes


def read(ctx):
    names = getattr(ctx["arch"], "INDEX_SCOPES", ())
    evs = scopes.scoped_events(ctx)
    if not names or not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    by = scopes.by_class(evs, ctx["cfg"], ctx["arch"])
    ns = sum(by.get(k, 0) for k in names)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
