"""From the load generator's records to what a user saw: tokens per
second, time to first token, gaps between tokens.  All on the client's
clock, over the whole window."""
from __future__ import annotations

from . import stats

GAP_EDGES_MS = (50, 100, 150, 200, 250, 300, 400, 500, 600, 700, 800,
                1000, 1500, 2000)


def window_view(records: list, t_open: int, t_close: int) -> dict:
    stamps, gaps, ttfts = [], [], []
    for r in records:
        if r["kind"] == "warm":
            continue
        st = r["stamps"]
        stamps.extend(st)
        gaps.extend(g / 1e6 for g in stats.gaps_in_window(st, t_open, t_close))
        if r["kind"] == "deal" and st and t_open <= st[0] < t_close:
            ttfts.append((st[0] - r["t_send"]) / 1e6)
    seconds = (t_close - t_open) / 1e9
    n_tok = sum(1 for t in stamps if t_open <= t < t_close)
    return {"seconds": seconds, "tokens": n_tok,
            "out_tokens_per_s": 1e9 * stats.rate_in_window(stamps, t_open,
                                                           t_close),
            "ttfts_ms": ttfts, "gaps_ms": gaps}


def thirds(records: list, t_open: int, t_close: int) -> list:
    """Tokens per second of the window's three thirds: a transient at
    the start shows as the first differing from the last."""
    stamps = [t for r in records if r["kind"] != "warm" for t in r["stamps"]]
    step = (t_close - t_open) // 3
    return [1e9 * stats.rate_in_window(stamps, t_open + i * step,
                                       t_open + (i + 1) * step)
            for i in range(3)]


def failures(records: list) -> tuple:
    """(attempted, failed, short): a request fails when it errored, ended
    for another reason than its length, or ended short of its
    max_tokens.  One cut at the window's close did neither."""
    failed = short = 0
    for r in records:
        if r["error"]:
            failed += 1
        elif r["finish"] is not None:
            if r["finish"] != "length":
                failed += 1
            elif len(r["tokens"]) != r["max_tokens"]:
                failed += 1
                short += 1
        elif not r["cut"]:
            failed += 1
    return len(records), failed, short


def end_to_end(view: dict) -> dict:
    g, t = view["gaps_ms"], view["ttfts_ms"]
    out = {"out_tokens_per_s": view["out_tokens_per_s"]}
    if t:
        out["ttft_p50_ms"] = stats.percentile(t, 50)
    if g:
        out["gap_p95_ms"] = stats.percentile(g, 95)
        out["gap_p99_ms"] = stats.percentile(g, 99)
        out["gap_top5_mean_ms"] = stats.top_share_mean(g, 0.05)
        out["gap_p50_ms"] = stats.percentile(g, 50)
    return out
