"""The comparison that decides ``correct``: what the timed path served,
against the configuration's plain reference.

Once the window has closed, a sample of the requests it finished (drawn
from the seed, the longest always in it) is run through the reference,
one whole forward pass per request over its prompt and its served
tokens.  Greedy decoding must pick, at every position, a token whose
reference logit is the reference's best or all but: the number compared
is the widest gap by which a served token's logit lies below the best.
"""
from __future__ import annotations

import numpy as np


def finished_in_window(records: list, t_open: int, t_close: int) -> list:
    return [r for r in records
            if r["kind"] in ("deal", "primer") and r["finish"] == "length"
            and not r["error"] and r["stamps"]
            and t_open <= r["stamps"][-1] < t_close]


def draw_sample(records: list, seed: int, n: int) -> list:
    """The longest finished request and n-1 others drawn from the seed."""
    if not records:
        return []
    order = sorted(range(len(records)),
                   key=lambda i: (records[i]["prompt_tokens"]
                                  + len(records[i]["tokens"]), i))
    longest = order[-1]
    rest = [i for i in range(len(records)) if i != longest]
    rng = np.random.default_rng([int(seed), 13])
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [records[longest]] + [records[rest[i]] for i in sorted(take)]


def served_gaps(reference, cfg: dict, seed: int, seqs: list, n_prompt: list,
                pad_to: int, n_score: int, lower: str | None = None) -> dict:
    """Per served token, how far its reference logit lies below the
    reference's best.  With ``lower``, the token is not the served one
    but the one the reference computed in that lower precision puts
    first at the same position (the control)."""
    score_from = [p - 1 for p in n_prompt]
    ref = reference.logits_at(cfg, seed, seqs, score_from, n_score, pad_to)
    low = None
    if lower is not None:
        low = reference.logits_at(cfg, seed, seqs, score_from, n_score,
                                  pad_to, lower=lower)
    gaps = []
    for i, (s, p) in enumerate(zip(seqs, n_prompt)):
        n = len(s) - p
        lg = ref[i, :n]
        best = lg.max(axis=-1)
        if low is None:
            tok = np.asarray(s[p:], np.int64)
        else:
            tok = low[i, :n].argmax(axis=-1)
        gaps.append(best - lg[np.arange(n), tok])
    g = np.concatenate(gaps) if gaps else np.zeros((0,), np.float32)
    if not np.all(np.isfinite(g)):
        return {"max": float("inf"), "mean": float("inf"),
                "nonzero_share": 1.0, "tokens": int(g.size)}
    return {"max": float(g.max()) if g.size else float("inf"),
            "mean": float(g.mean()) if g.size else float("inf"),
            "nonzero_share": float((g > 0).mean()) if g.size else 1.0,
            "tokens": int(g.size)}


def verdict(numbers: list) -> bool:
    """numbers: [{"name", "value", "limit", "sense"}]; sense "max" means
    value <= limit, "min" means value >= limit."""
    ok = True
    for n in numbers:
        v, lim = n["value"], n["limit"]
        good = (v <= lim) if n["sense"] == "max" else (v >= lim)
        ok = ok and bool(good) and v == v
    return ok
