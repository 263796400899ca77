"""Time under the scope in which a step program writes a layer's new
rows into the page pools (the first of the architecture's
``POOL_SCOPES``, which every shapes file lists as the page writes and
then the kernels; ``tests/test_benchmark_seam.py`` holds each file to
that order: the XLA row scatter, or the page writer's launch with the
padding of its rows) over device busy time."""
from harness import scopes


def read(ctx):
    evs = scopes.scoped_events(ctx)
    if not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    ns = scopes.by_class(evs, ctx["cfg"], ctx["arch"]).get(
        ctx["arch"].POOL_SCOPES[0], 0)
    return 100.0 * ns / (ctx["trace"]["busy_s"] * 1e9) if ns > 0 else None
