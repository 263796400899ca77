"""Time in the WINDOW layers' attention launches over device busy time,
from the device trace: operations named after one of the kernels the
architecture's shapes file lists under ``WINDOW_KERNELS`` (a window
layer's launch has a kernel name of its own; ``attn.device_share`` is
all attention kernels, these among them).  An architecture without
window layers gives nothing to read."""
from types import SimpleNamespace

from harness import scopes


def read(ctx):
    tr = ctx["trace"]
    names = getattr(ctx["arch"], "WINDOW_KERNELS", ())
    if tr is None or tr["busy_s"] <= 0 or not names:
        return None
    ns = scopes.kernel_ns(tr["events"], SimpleNamespace(KERNELS=names))
    return 100.0 * ns / 1e9 / tr["busy_s"] if ns > 0 else None
