"""Time in matrix products over device busy time: the self time of every
device operation that holds a dot, whatever its scope (XLA fuses across
scopes), everything else under the step program's matrix-product scopes
(``qkv``, ``o_proj``, ``mlp``, ``head``: the architecture's shapes file
lists them), and the scan's own operations (``layers``: the per-layer
slices and copies of the stacked weights, which only the products
read).  Also prints the traced
window's busy time by scope (``[bench] scopes``: the program's phases, the
scan's own work, pool-shaped copies, and what carries no scope), which
adds up to 100."""
import json

from harness import scopes


def read(ctx):
    evs = scopes.scoped_events(ctx)
    if not evs or ctx["trace"]["busy_s"] <= 0:
        return None
    busy_ns = ctx["trace"]["busy_s"] * 1e9
    shares = {k: round(100.0 * v / busy_ns, 3) for k, v in sorted(
        scopes.by_class(evs, ctx["cfg"], ctx["arch"]).items(),
        key=lambda kv: -kv[1])}
    print("[bench] scopes", json.dumps(shares), flush=True)
    ns = scopes.matmul_ns(evs, ctx["cfg"], ctx["arch"])
    return 100.0 * ns / busy_ns if ns > 0 else None
