"""What the algorithm needs, counted from shapes: the operations and the
bytes of attention over the rows' real K/V lengths.  Not what a kernel's
grid walks: a share of the roofline computed from these rises when a PR
stops walking the static ``max_model_len``."""
from __future__ import annotations


def attention_row(n_q: int, kv_len: int, *, heads: int, kv_heads: int,
                  head_dim: int, bytes_per: int = 2) -> tuple:
    """(operations, bytes) of causal attention, one layer, for one row
    that brings ``n_q`` query tokens and ends at ``kv_len`` keys: query i
    (0-based) sees kv_len - n_q + i + 1 keys.

    Operations: a multiply-add for q.k and one for p.v, each 2 ops per
    head element: 4 * heads * head_dim per (query, key) pair.
    Bytes: K and V of the row read once (kv_len * kv_heads * head_dim
    each), the new K and V written (n_q rows of each), q read and the
    output written (n_q * heads * head_dim each)."""
    pairs = n_q * kv_len - n_q * (n_q - 1) // 2
    ops = 4 * heads * head_dim * pairs
    kv = 2 * kv_len * kv_heads * head_dim
    new_kv = 2 * n_q * kv_heads * head_dim
    qo = 2 * n_q * heads * head_dim
    return ops, (kv + new_kv + qo) * bytes_per


def attention_total(rows, *, layers: int, heads: int, kv_heads: int,
                    head_dim: int, bytes_per: int = 2) -> tuple:
    """Sum over (n_q, kv_len) rows and layers."""
    ops = byt = 0
    for n_q, kv_len in rows:
        o, b = attention_row(n_q, kv_len, heads=heads, kv_heads=kv_heads,
                             head_dim=head_dim, bytes_per=bytes_per)
        ops += o
        byt += b
    return ops * layers, byt * layers


def least_seconds(ops: float, byt: float, peak: dict) -> tuple:
    """The least time the chip needs and which bound sets it."""
    t_ops = ops / peak["bf16_flops"]
    t_byt = byt / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_byt else (t_byt, "memory")
