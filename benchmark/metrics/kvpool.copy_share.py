"""Time in copies of the K/V pool that the program did not ask for, over
device busy time: operations whose result has the pool's shape (the
whole pool or one layer of it, as the architecture's shapes file gives
them) and that are not under the scopes of the pool's own writers (the
page writes and the kernel)."""
from harness import scopes


def read(ctx):
    evs = scopes.scoped_events(ctx)
    if not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    ns = scopes.by_class(evs, ctx["cfg"], ctx["arch"]).get(
        scopes.POOL_COPY, 0)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
