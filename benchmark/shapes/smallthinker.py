"""Shapes and counts of a decoder that mixes global attention without
positions and sliding-window attention with rotary positions, every
layer with sparse ReGLU experts routed from the pre-attention norm
(``references/smallthinker.py`` has the equations), as the engine serves
it.  Imports nothing of the program.  What every shapes file states is
listed in ``shapes/llama_dense.py``; what differs here:

- layers are of two kinds by ``sliding_window_layout`` (its first
  ``num_hidden_layers`` entries): a global layer's K/V pages live in one
  pool ``[Lg, num_blocks, kvh, block, d]``, a window layer's in another
  ``[Lw, Nw, kvh, block, d]`` whose size the ENGINE derives (``Nw``
  below is the same arithmetic, for the trace reader and the tests);
- ``attention_row`` counts for a window layer only the (query, key)
  pairs and the bytes INSIDE the window: the roofline reads the work the
  algorithm calls for, not what a kernel that ignored the window would
  do.  ``window_attention_row`` is the window layers' part alone
  (``metrics/attn.window_roofline_share.py``); ``WINDOW_KERNELS`` names
  their launches, which a device trace tells from the global layers' by
  the kernel's name;
- the routed experts run as a grouped product outside XLA's dots
  (``moe_experts``: ``metrics/moe.*`` count them through
  ``expert_products``, three matrices an expert; ``step_matmuls`` does
  not)."""
from __future__ import annotations

TOP = (("embed", "embedding"), ("norm_f", "norm"), ("head", "matrix"))
LAYER = (("ln1", "norm"), ("router", "matrix"), ("wq", "matrix"),
         ("wk", "matrix"), ("wv", "matrix"), ("wo", "matrix"),
         ("ln2", "norm"), ("e_gate", "matrix"), ("e_up", "matrix"),
         ("e_down", "matrix"))

WINDOW_KERNELS = ("ragged_paged_attention_window",)
KERNELS = ("ragged_paged_attention",) + WINDOW_KERNELS
SCOPES = ("embed", "norm", "qkv", "rope", "kv_write", "attn", "attn_window",
          "o_proj", "router", "moe_dispatch", "moe_experts", "moe_combine",
          "head", "sample")
LOOP = "layers"
MATMUL_SCOPES = ("qkv", "o_proj", "router", "head")
SAMPLE_SCOPES = ("sample",)
POOL_SCOPES = ("kv_write", "attn", "attn_window")
MOE_SCOPES = ("router", "moe_dispatch", "moe_experts", "moe_combine")
EXPERT_SCOPES = ("moe_experts",)


def dims(cfg: dict) -> dict:
    L = int(cfg["num_hidden_layers"])
    window = [bool(w) for w in cfg["sliding_window_layout"][:L]]
    if len(window) != L:
        raise ValueError(f"sliding_window_layout has {len(window)} entries "
                         f"for {L} layers")
    return {"H": int(cfg["hidden_size"]),
            "nh": int(cfg["num_attention_heads"]),
            "kvh": int(cfg["num_key_value_heads"]),
            "d": int(cfg["head_dim"]),
            "E": int(cfg["moe_num_primary_experts"]),
            # every layer holds all its experts and none is dense (the
            # names ``metrics/moe.load_max_over_mean.py`` reads)
            "held": int(cfg["moe_num_primary_experts"]), "dense": 0,
            "k": int(cfg["moe_num_active_primary_experts"]),
            "F": int(cfg["moe_ffn_hidden_size"]),
            "W": int(cfg["sliding_window_size"]),
            "window": window, "Lw": sum(window), "Lg": L - sum(window),
            "theta": float(cfg["rope_theta"]),
            "V": int(cfg["vocab_size"]), "L": L}


def _shape(m: dict, name: str) -> tuple:
    H, nh, kvh, d, E, F = m["H"], m["nh"], m["kvh"], m["d"], m["E"], m["F"]
    return {"ln1": (H,), "ln2": (H,), "norm_f": (H,), "router": (H, E),
            "wq": (H, nh * d), "wk": (H, kvh * d), "wv": (H, kvh * d),
            "wo": (nh * d, H), "e_gate": (E, H, F), "e_up": (E, H, F),
            "e_down": (E, F, H), "embed": (m["V"], H),
            "head": (H, m["V"])}[name]


def leaves(cfg: dict) -> list:
    """[(name, layer or None, shape, kind)]; a leaf's place in the list
    is the index its draw is folded from."""
    m = dims(cfg)
    out = [(n, None, _shape(m, n), k) for n, k in TOP]
    for i in range(m["L"]):
        out += [(n, i, _shape(m, n), k) for n, k in LAYER]
    return out


def window_blocks(cfg: dict) -> int:
    """Pages of the window layers' pool as the engine derives them: the
    most ``max_num_seqs`` running sequences hold at once (a window, the
    longest chunk in flight and one page for a window that starts inside
    a page, each), and the null page."""
    s, m = cfg["serving"], dims(cfg)
    bs = int(s["block_size"])
    per_seq = min(-(-int(s["max_model_len"]) // bs),
                  -(-m["W"] // bs) + -(-int(s["max_prefill_tokens"]) // bs)
                  + 1)
    return 1 + int(s["max_num_seqs"]) * per_seq


def pool_shapes(cfg: dict) -> set:
    """Dimension lists of both pairs of pools and of one layer of each:
    [Lg, num_blocks, kvh, block, d] and [Lw, Nw, kvh, block, d], each
    also without its layer axis and with a leading 1."""
    s, m = cfg["serving"], dims(cfg)
    out = set()
    for layers, pages in ((m["Lg"], int(s["num_blocks"])),
                          (m["Lw"], window_blocks(cfg))):
        one = [pages, m["kvh"], int(s["block_size"]), m["d"]]
        out |= {tuple([layers] + one), tuple(one), tuple([1] + one)}
    return out


def layer_dense_weights(m: dict) -> int:
    """Matrix elements of a layer that XLA's dots read: q and o are
    H x nh*d, k and v are H x kvh*d, the router is H x E."""
    H, nh, kvh, d = m["H"], m["nh"], m["kvh"], m["d"]
    return 2 * H * nh * d + 2 * H * kvh * d + H * m["E"]


def step_matmuls(cfg: dict, tokens: int, logit_rows: int, *,
                 bytes_per: int = 2, logit_bytes: int = 4) -> tuple:
    """(operations, bytes) of the products that run as XLA dots in one
    step of ``tokens`` real query tokens and ``logit_rows`` scored rows:
    the q, k, v and output projections, the router and the head.  The
    routed experts are a grouped product of their own and are not here
    (``moe.roofline_share``).

    Operations: 2 per weight element per token.  Bytes: those weights
    once a step, per token and layer the activations each product reads
    and writes (the router's logits float32), per logit row its hidden
    state in and its float32 logits out."""
    m = dims(cfg)
    H, nh, kvh, d, V, L = m["H"], m["nh"], m["kvh"], m["d"], m["V"], m["L"]
    w = layer_dense_weights(m)
    ops = 2 * tokens * w * L + 2 * logit_rows * H * V
    acts = (H + (nh + 2 * kvh) * d) + (nh * d + H)
    byt = (w * L + H * V) * bytes_per \
        + tokens * L * (acts * bytes_per + H * bytes_per
                        + m["E"] * logit_bytes) \
        + logit_rows * (H * bytes_per + V * logit_bytes)
    return ops, byt


def _row(m: dict, n_q: int, kv_len: int, window, bytes_per: int) -> tuple:
    """One layer's (operations, bytes) for a row of ``n_q`` queries that
    ends at ``kv_len`` keys; ``window``: the keys a query sees, its own
    among them (None: all up to its own)."""
    nh, kvh, d = m["nh"], m["kvh"], m["d"]
    first = kv_len - n_q                      # the first query's position
    if window is None or kv_len <= window:
        pairs = n_q * kv_len - n_q * (n_q - 1) // 2
        keys = kv_len
    else:
        # query at position p sees min(p + 1, window) keys
        short = max(0, min(n_q, window - 1 - first))   # p + 1 < window
        pairs = short * (first + 1) + short * (short - 1) // 2 \
            + (n_q - short) * window
        keys = kv_len - max(0, first - window + 1)
    ops = 4 * nh * d * pairs
    byt = (2 * keys * kvh * d + 2 * n_q * kvh * d + 2 * n_q * nh * d) \
        * bytes_per
    return ops, byt


def window_attention_row(cfg: dict, n_q: int, kv_len: int, *,
                         bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the WINDOW layers' attention for one row:
    only the pairs and the keys inside the window."""
    m = dims(cfg)
    ops, byt = _row(m, n_q, kv_len, m["W"], bytes_per)
    return ops * m["Lw"], byt * m["Lw"]


def attention_row(cfg: dict, n_q: int, kv_len: int, *,
                  bytes_per: int = 2) -> tuple:
    """(operations, bytes) of attention, all layers, for one row of
    ``n_q`` query tokens that ends at ``kv_len`` keys: the global layers
    see every key up to the query's own, the window layers the window's.

    Operations: a multiply-add for q.k and one for p.v, 4 * heads *
    head_dim per (query, key) pair.  Bytes: the K and V rows a layer's
    queries see read once, the new K and V written, q read and the
    output written."""
    m = dims(cfg)
    og, bg = _row(m, n_q, kv_len, None, bytes_per)
    ow, bw = window_attention_row(cfg, n_q, kv_len, bytes_per=bytes_per)
    return og * m["Lg"] + ow, bg * m["Lg"] + bw


def expert_products(cfg: dict, pairs: int, touched: int, *,
                    bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the routed experts' grouped products for
    ``pairs`` token-expert pairs over ``touched`` (layer, expert) pairs
    that got at least one token (both summed over layers, as the engine
    counts them).

    Operations: a pair meets its expert's three matrices (gate, up,
    down) once, 2 ops a multiply-add.  Bytes: the three matrices of each
    touched expert once, and per pair the hidden state in, the two
    F-wide products out and the gated one in again, and the hidden-wide
    result out."""
    m = dims(cfg)
    per_expert = 3 * m["H"] * m["F"]
    ops = 2 * pairs * per_expert
    byt = (touched * per_expert
           + pairs * (m["H"] + 3 * m["F"] + m["H"])) * bytes_per
    return ops, byt
