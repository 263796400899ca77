"""Time under the step program's ``sample`` scope (the logit processors,
the draw, the finiteness flag) over device busy time."""
from harness import scopes


def read(ctx):
    evs = scopes.scoped_events(ctx)
    if not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    ns = scopes.by_class(evs, ctx["cfg"]).get("sample", 0)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
