"""Pages a launch's rows hold in a window layer over the pages one table
for all layers would hold for them, in percent, averaged over the
launches made inside the window: ``kv_pages_window`` over
``kv_pages_uniform`` on ``engine.device_launch``.  100 says nothing is
given back; a sequence of 12,000 keys under a window of 4,096 reads 34.
A program without the two counts gives nothing to read."""
from harness import spans as S


def read(ctx):
    shares = []
    for s in S.in_window(S.named(ctx["spans"], "engine.device_launch", "X"),
                         ctx["t_open"], ctx["t_close"]):
        a = s["args"]
        if "kv_pages_window" in a and a.get("kv_pages_uniform"):
            shares.append(100.0 * a["kv_pages_window"]
                          / a["kv_pages_uniform"])
    return sum(shares) / len(shares) if shares else None
