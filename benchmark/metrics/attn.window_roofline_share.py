"""The least time the chip needs for the WINDOW layers' attention, at
the rows' real K/V lengths and counting only the (query, key) pairs and
the bytes inside the window, over the traced time of their launches
(``WINDOW_KERNELS`` of the architecture's shapes file).  The rows are
those of the steps completed inside the traced window, as for
``attn.roofline_share`` (which stays the whole: every layer, every
attention kernel); operations and bytes come from the shapes file's
``window_attention_row``.  An architecture without window layers gives
nothing to read."""
from types import SimpleNamespace

from harness import costs, peaks, scopes, spans as S


def read(ctx):
    tr, arch = ctx["trace"], ctx["arch"]
    names = getattr(arch, "WINDOW_KERNELS", ())
    if tr is None or not names or not hasattr(arch, "window_attention_row"):
        return None
    ns = scopes.kernel_ns(tr["events"], SimpleNamespace(KERNELS=names))
    if ns <= 0:
        return None
    h0, h1 = tr["host_window"]
    rows = S.attention_rows(ctx["spans"], h0, h1)
    if not rows:
        return None
    ops = byt = 0
    for n_q, kv_len in rows:
        o, b = arch.window_attention_row(ctx["cfg"], n_q, kv_len)
        ops += o
        byt += b
    least, _bound = costs.least_seconds(ops, byt,
                                        peaks.peaks(ctx["device_kind"]))
    return 100.0 * least / (ns / 1e9)
