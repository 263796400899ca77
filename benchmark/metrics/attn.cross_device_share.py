"""Time in the CROSS layers' attention launches over device busy time,
from the device trace: operations named after one of the kernels the
architecture's shapes file lists under ``CROSS_KERNELS`` (a layer that
attends over another layer's pages launches under a kernel name of its
own; ``attn.device_share`` is all attention kernels, these among them).
An architecture without such layers gives nothing to read."""
from types import SimpleNamespace

from harness import scopes


def read(ctx):
    tr = ctx["trace"]
    names = getattr(ctx["arch"], "CROSS_KERNELS", ())
    if tr is None or tr["busy_s"] <= 0 or not names:
        return None
    ns = scopes.kernel_ns(tr["events"], SimpleNamespace(KERNELS=names))
    return 100.0 * ns / 1e9 / tr["busy_s"] if ns > 0 else None
