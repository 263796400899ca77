"""Time in copies of the K/V pool that the program did not ask for, over
device busy time: operations whose result has the pool's shape ([L,
num_blocks, kvh, block, d] or one layer of it) and that are not under
``kv_write`` (the page writes) or ``attn`` (the kernel)."""
from harness import scopes


def read(ctx):
    evs = scopes.scoped_events(ctx)
    if not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    ns = scopes.by_class(evs, ctx["cfg"]).get(scopes.POOL_COPY, 0)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
