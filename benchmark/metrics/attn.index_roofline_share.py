"""The least time the chip needs for the indexer's score products, at
the rows' real K/V lengths (every (query, key) pair a query sees,
``index_n_heads`` heads of ``index_head_dim``, and the row's index keys
read once: the shapes file's ``index_row``, counted from the launches'
rows whatever implements the product), over the traced time under the
indexer's scope (``INDEX_SCOPES``: its projections ride in the time and
not in the count, so the share reads low rather than high).  The rows
are those of the steps completed inside the traced window, as for
``attn.roofline_share``.  An architecture without an indexer gives
nothing to read."""
from harness import costs, peaks, scopes, spans as S


def read(ctx):
    tr, arch = ctx["trace"], ctx["arch"]
    names = getattr(arch, "INDEX_SCOPES", ())
    if tr is None or not names or not hasattr(arch, "index_row"):
        return None
    evs = scopes.scoped_events(ctx)
    if not evs:
        return None
    by = scopes.by_class(evs, ctx["cfg"], arch)
    ns = sum(by.get(k, 0) for k in names)
    if ns <= 0:
        return None
    h0, h1 = tr["host_window"]
    rows = S.attention_rows(ctx["spans"], h0, h1)
    if not rows:
        return None
    ops = byt = 0
    for n_q, kv_len in rows:
        o, b = arch.index_row(ctx["cfg"], n_q, kv_len)
        ops += o
        byt += b
    least, _bound = costs.least_seconds(ops, byt,
                                        peaks.peaks(ctx["device_kind"]))
    return 100.0 * least / (ns / 1e9)
