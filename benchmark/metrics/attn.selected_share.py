"""The share of the keys its queries SAW that an indexed layer's queries
SELECTED, in percent, averaged over the launches made inside the window:
``index_keys_selected`` over ``index_keys_visible`` on
``engine.device_launch`` (host-side sums over the launch's rows: a query
at position p sees p + 1 keys and attends to ``min(p + 1, index_topk)``
of them).  100 says the selection discards nothing (every row is under
``index_topk``); a 512-token chunk at 9,500 keys under a top-2048 reads
about 22.  A program without the two counts gives nothing to read."""
from harness import spans as S


def read(ctx):
    shares = []
    for s in S.in_window(S.named(ctx["spans"], "engine.device_launch", "X"),
                         ctx["t_open"], ctx["t_close"]):
        a = s["args"]
        if "index_keys_selected" in a and a.get("index_keys_visible"):
            shares.append(100.0 * a["index_keys_selected"]
                          / a["index_keys_visible"])
    return sum(shares) / len(shares) if shares else None
