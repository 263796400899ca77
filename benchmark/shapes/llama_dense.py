"""Shapes and counts of the LLaMA-style dense decoder (RMSNorm, rotary
positions, grouped-query attention, SwiGLU, an output head of its own),
as the engine serves it: what the harness has to know of *this*
architecture and of no other.  Imports nothing of the program.

One of the three files that ``"reference": "llama_dense"`` in a
configuration's file finds (``harness/spec.py``): this one, the plain
reference ``references/llama_dense.py`` and the builder
``builders/llama_dense.py``.  What every such file states:

``dims(cfg)``       the sizes its own counts and its reference use
``leaves(cfg)``     every weight, in the order that gives each its index
``pool_shapes(cfg)``  result shapes of the K/V pool in a device trace
``KERNELS``         names of its kernels' instructions (``name=`` of a
                    ``pallas_call``), without the index XLA appends
``SCOPES``, ``LOOP``  the ``jax.named_scope`` names of its step programs
``MATMUL_SCOPES``, ``SAMPLE_SCOPES``, ``POOL_SCOPES``  which of them count
                    as matrix-product time, as the sampling epilogue, and
                    as the pool's own writers
``step_matmuls(cfg, tokens, logit_rows)``, ``attention_row(cfg, n_q,
kv_len)``           (operations, bytes) the algorithm needs
"""
from __future__ import annotations

# (name, kind) in index order: the top of the model first, then each
# layer's nine.  A kind says how ``harness/weights.py`` draws the leaf.
TOP = (("embed", "embedding"), ("norm_f", "norm"), ("head", "matrix"))
LAYER = (("ln1", "norm"), ("wq", "matrix"), ("wk", "matrix"),
         ("wv", "matrix"), ("wo", "matrix"), ("ln2", "norm"),
         ("gate", "matrix"), ("up", "matrix"), ("down", "matrix"))

KERNELS = ("ragged_paged_attention", "ragged_paged_attention_q8")
SCOPES = ("embed", "norm", "qkv", "rope", "kv_write", "attn", "o_proj",
          "mlp", "head", "sample")
LOOP = "layers"                     # the scan over layers, its own work
MATMUL_SCOPES = ("qkv", "o_proj", "mlp", "head")
SAMPLE_SCOPES = ("sample",)
POOL_SCOPES = ("kv_write", "attn")  # the page writes and the kernel


def dims(cfg: dict) -> dict:
    h = int(cfg["hidden_size"])
    nh = int(cfg["num_attention_heads"])
    return {"H": h, "nh": nh, "kvh": int(cfg["num_key_value_heads"]),
            "d": h // nh, "F": int(cfg["intermediate_size"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"])}


def _shape(m: dict, name: str) -> tuple:
    H, nh, kvh, d, F, V = m["H"], m["nh"], m["kvh"], m["d"], m["F"], m["V"]
    return {"ln1": (H,), "ln2": (H,), "norm_f": (H,),
            "wq": (H, nh * d), "wk": (H, kvh * d), "wv": (H, kvh * d),
            "wo": (nh * d, H), "gate": (H, F), "up": (H, F),
            "down": (F, H), "embed": (V, H), "head": (H, V)}[name]


def leaves(cfg: dict) -> list:
    """[(name, layer or None, shape, kind)]; a leaf's place in the list
    is the index its draw is folded from."""
    m = dims(cfg)
    out = [(n, None, _shape(m, n), k) for n, k in TOP]
    for i in range(m["L"]):
        out += [(n, i, _shape(m, n), k) for n, k in LAYER]
    return out


def pool_shapes(cfg: dict) -> set:
    """Dimension lists of the K/V pool and of one layer of it, as a
    result shape prints them: [L, num_blocks, kvh, block, d] and
    [num_blocks, kvh, block, d] (also with a leading 1)."""
    s, m = cfg["serving"], dims(cfg)
    one = [int(s["num_blocks"]), m["kvh"], int(s["block_size"]), m["d"]]
    return {tuple([m["L"]] + one), tuple(one), tuple([1] + one)}


def layer_weights(m: dict) -> int:
    """Matrix elements of one decoder layer: q and o are H x nh*d, k and
    v are H x kvh*d, gate, up and down are H x F."""
    H, nh, kvh, d, F = m["H"], m["nh"], m["kvh"], m["d"], m["F"]
    return 2 * H * nh * d + 2 * H * kvh * d + 3 * H * F


def step_matmuls(cfg: dict, tokens: int, logit_rows: int, *,
                 bytes_per: int = 2, logit_bytes: int = 4) -> tuple:
    """(operations, bytes) of the dense matrix products of one step that
    carries ``tokens`` query tokens (the real ones, not the bucket's
    padding) and scores ``logit_rows`` of them against the vocabulary.

    Operations: a multiply-add per weight element per token, 2 ops.
    Bytes: the layers' weights and the head once each, however many
    tokens ride (that is what batching buys); per token and layer the
    activations each product reads and writes (x into q, k, v; the heads
    into o; x into gate and up; the F-wide product into down); per logit
    row its hidden state in and its logits out (float32)."""
    m = dims(cfg)
    H, nh, kvh, d, F, V, L = (m["H"], m["nh"], m["kvh"], m["d"], m["F"],
                              m["V"], m["L"])
    w = layer_weights(m)
    ops = 2 * tokens * w * L + 2 * logit_rows * H * V
    acts = (H + (nh + 2 * kvh) * d) + (nh * d + H) + (H + 2 * F) + (F + H)
    byt = (w * L + H * V) * bytes_per \
        + tokens * acts * L * bytes_per \
        + logit_rows * (H * bytes_per + V * logit_bytes)
    return ops, byt


def attention_row(cfg: dict, n_q: int, kv_len: int, *,
                  bytes_per: int = 2) -> tuple:
    """(operations, bytes) of causal attention, all layers, for one row
    that brings ``n_q`` query tokens and ends at ``kv_len`` keys: query i
    (0-based) sees kv_len - n_q + i + 1 keys.  What the algorithm needs
    at the row's real K/V length, not what a kernel's grid walks.

    Operations: a multiply-add for q.k and one for p.v, each 2 ops per
    head element: 4 * heads * head_dim per (query, key) pair.
    Bytes: K and V of the row read once (kv_len * kv_heads * head_dim
    each), the new K and V written (n_q rows of each), q read and the
    output written (n_q * heads * head_dim each)."""
    m = dims(cfg)
    nh, kvh, d, L = m["nh"], m["kvh"], m["d"], m["L"]
    pairs = n_q * kv_len - n_q * (n_q - 1) // 2
    ops = 4 * nh * d * pairs
    kv = 2 * kv_len * kvh * d
    new_kv = 2 * n_q * kvh * d
    qo = 2 * n_q * nh * d
    return ops * L, (kv + new_kv + qo) * bytes_per * L
