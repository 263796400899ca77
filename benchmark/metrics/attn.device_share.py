"""Time in the architecture's attention kernels over device busy time,
from the device trace: operations named after one of the kernels its
shapes file lists (the ``name=`` of a ``pallas_call``)."""
from harness import scopes


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr["busy_s"] <= 0:
        return None
    ns = scopes.kernel_ns(tr["events"], ctx["arch"])
    return 100.0 * ns / 1e9 / tr["busy_s"] if ns > 0 else None
