"""The least time the chip needs for the attention the algorithm calls
for, at the rows' real K/V lengths, over the kernels' traced time.  The
rows are those of the steps completed inside the traced window (from the
Tracer's request events); operations and bytes come from the
architecture's shapes file (``attention_row``), the kernels are found by
the names it lists, the peaks come from ``harness/peaks.py``."""
from harness import costs, peaks, scopes, spans as S


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    ns = scopes.kernel_ns(tr["events"], ctx["arch"])
    if ns <= 0:
        return None
    h0, h1 = tr["host_window"]
    rows = S.attention_rows(ctx["spans"], h0, h1)
    if not rows:
        return None
    ops, byt = costs.attention_total(ctx["arch"], ctx["cfg"], rows)
    least, _bound = costs.least_seconds(ops, byt,
                                        peaks.peaks(ctx["device_kind"]))
    return 100.0 * least / (ns / 1e9)
