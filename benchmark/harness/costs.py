"""From what the algorithm needs to the least time the chip could take.
The operations and bytes themselves are the architecture's to count
(``shapes/<name>.py``: ``attention_row`` over a row's real K/V length,
``step_matmuls`` over a step's real tokens and used logit rows); this
file sums them and holds them against the peaks.  A share of the
roofline computed from these rises when a PR stops walking padding."""
from __future__ import annotations


def least_seconds(ops: float, byt: float, peak: dict) -> tuple:
    """The least time the chip needs and which bound sets it."""
    t_ops = ops / peak["bf16_flops"]
    t_byt = byt / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_byt else (t_byt, "memory")


def attention_total(arch, cfg: dict, rows) -> tuple:
    """(operations, bytes) of attention summed over (n_q, kv_len) rows."""
    ops = byt = 0
    for n_q, kv_len in rows:
        o, b = arch.attention_row(cfg, n_q, kv_len)
        ops += o
        byt += b
    return ops, byt


def matmul_least_seconds(arch, cfg: dict, steps, peak: dict) -> float:
    """The least time the chip needs for the matrix products of these
    (tokens, logit_rows) steps: each step's own bound (operations or
    bytes, whichever is the larger), summed: a step cannot borrow another
    step's slack."""
    return sum(least_seconds(*arch.step_matmuls(cfg, t, r), peak)[0]
               for t, r in steps)
