"""The least time the chip needs for the attention the algorithm calls
for, at the rows' real K/V lengths, over the kernel's traced time.  The
rows are those of the steps completed inside the traced window (from the
Tracer's request events); operations and bytes come from
``harness/costs.py``, the peaks from ``harness/peaks.py``."""
from harness import costs, peaks, spans as S, weights as W, xplane as X


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    ns = X.attention_kernel_ns(tr["events"], ctx["cfg"])
    if ns <= 0:
        return None
    m = W.dims(ctx["cfg"])
    h0, h1 = tr["host_window"]
    rows = S.attention_rows(ctx["spans"], h0, h1)
    if not rows:
        return None
    ops, byt = costs.attention_total(rows, layers=m["L"], heads=m["nh"],
                                     kv_heads=m["kvh"], head_dim=m["d"])
    least, _bound = costs.least_seconds(ops, byt,
                                        peaks.peaks(ctx["device_kind"]))
    return 100.0 * least / (ns / 1e9)
