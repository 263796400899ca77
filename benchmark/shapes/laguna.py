"""Shapes and counts of a decoder whose full-attention and sliding-window
layers differ in their query heads and their rotary, with a gated
attention output, a leading dense layer and sparse layers of many small
experts (all held) beside a shared one (``references/laguna.py`` has the
equations), as the engine serves it.  Imports nothing of the program.
What every shapes file states is listed in ``shapes/llama_dense.py``;
what differs here:

- layers are of two kinds by ``layer_types`` (its first
  ``num_hidden_layers`` entries), and a layer's query heads are
  ``num_attention_heads_per_layer[i]``: ``wq``, ``wg`` and ``wo`` of a
  full layer and of a sliding layer differ in shape.  A full layer's K/V
  pages live in one pool ``[Lg, num_blocks, kvh, block, d]``, a sliding
  layer's in another ``[Lw, Nw, kvh, block, d]`` whose size the ENGINE
  derives (``window_blocks`` below is the same arithmetic);
- ``attention_row`` counts a full layer's heads over every (query, key)
  pair and a sliding layer's over the pairs INSIDE the window; a head
  that is half rotated costs the attention what a whole one does (the
  rotation is under ``rope``, not in the kernel).
  ``window_attention_row`` is the sliding layers' part alone;
  ``WINDOW_KERNELS`` names their launches;
- the gate's product ``h W_g`` and its sigmoid-multiply run under
  ``attn_gate`` (``metrics/attn.gate_device_share.py``) and count with
  the matmuls, as do layer 0's SwiGLU (``mlp``), the router and the
  shared expert; the routed experts run as a grouped product outside
  XLA's dots (``moe_experts``: ``metrics/moe.*`` count them through
  ``expert_products``, three matrices an expert; ``step_matmuls`` does
  not)."""
from __future__ import annotations

TOP = (("embed", "embedding"), ("norm_f", "norm"), ("head", "matrix"))
ATTENTION = (("ln1", "norm"), ("wq", "matrix"), ("wk", "matrix"),
             ("wv", "matrix"), ("wo", "matrix"), ("wg", "matrix"),
             ("ln2", "norm"))
DENSE = (("gate", "matrix"), ("up", "matrix"), ("down", "matrix"))
EXPERTS = (("router", "matrix"), ("e_gate", "matrix"), ("e_up", "matrix"),
           ("e_down", "matrix"), ("s_gate", "matrix"), ("s_up", "matrix"),
           ("s_down", "matrix"))

WINDOW_KERNELS = ("ragged_paged_attention_window",)
KERNELS = ("ragged_paged_attention",) + WINDOW_KERNELS
SCOPES = ("embed", "norm", "qkv", "rope", "kv_write", "attn", "attn_window",
          "attn_gate", "o_proj", "router", "moe_dispatch", "moe_experts",
          "moe_combine", "shared_expert", "mlp", "head", "sample")
LOOP = "layers"
MATMUL_SCOPES = ("qkv", "attn_gate", "o_proj", "router", "shared_expert",
                 "mlp", "head")
SAMPLE_SCOPES = ("sample",)
POOL_SCOPES = ("kv_write", "attn", "attn_window")
MOE_SCOPES = ("router", "moe_dispatch", "moe_experts", "moe_combine")
EXPERT_SCOPES = ("moe_experts",)
GATE_SCOPES = ("attn_gate",)


def dims(cfg: dict) -> dict:
    L = int(cfg["num_hidden_layers"])
    window = [t == "sliding_attention" for t in cfg["layer_types"][:L]]
    heads = [int(n) for n in cfg["num_attention_heads_per_layer"][:L]]
    sparse = [t == "sparse" for t in cfg["mlp_layer_types"][:L]]
    if not len(window) == len(heads) == len(sparse) == L:
        raise ValueError(f"the per-layer lists hold {len(window)}, "
                         f"{len(heads)} and {len(sparse)} entries for {L} "
                         "layers")
    if not cfg.get("gating", False):
        raise ValueError("the leaves below hold a gate: gating is true")
    return {"H": int(cfg["hidden_size"]), "heads": heads,
            "kvh": int(cfg["num_key_value_heads"]),
            "d": int(cfg["head_dim"]),
            "F": int(cfg["intermediate_size"]),
            "E": int(cfg["num_experts"]),
            # every sparse layer holds all its experts (the names
            # ``metrics/moe.load_max_over_mean.py`` reads)
            "held": int(cfg["num_experts"]), "dense": L - sum(sparse),
            "k": int(cfg["num_experts_per_tok"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "Fs": int(cfg["shared_expert_intermediate_size"]),
            "scaling": float(cfg["moe_routed_scaling_factor"]),
            "W": int(cfg["sliding_window"]),
            "window": window, "sparse": sparse,
            "Lw": sum(window), "Lg": L - sum(window),
            "rope": {False: dict(cfg["rope_parameters"]["full_attention"]),
                     True: dict(cfg["rope_parameters"]["sliding_attention"])},
            "V": int(cfg["vocab_size"]), "L": L}


def _shape(m: dict, name: str, nh: int) -> tuple:
    H, kvh, d, E = m["H"], m["kvh"], m["d"], m["E"]
    F, Fe, Fs = m["F"], m["Fe"], m["Fs"]
    return {"ln1": (H,), "ln2": (H,), "norm_f": (H,),
            "wq": (H, nh * d), "wk": (H, kvh * d), "wv": (H, kvh * d),
            "wo": (nh * d, H), "wg": (H, nh * d),
            "gate": (H, F), "up": (H, F), "down": (F, H),
            "router": (H, E), "e_gate": (E, H, Fe), "e_up": (E, H, Fe),
            "e_down": (E, Fe, H), "s_gate": (H, Fs), "s_up": (H, Fs),
            "s_down": (Fs, H), "embed": (m["V"], H),
            "head": (H, m["V"])}[name]


def layer_names(m: dict, i: int) -> tuple:
    return ATTENTION + (EXPERTS if m["sparse"][i] else DENSE)


def leaves(cfg: dict) -> list:
    """[(name, layer or None, shape, kind)]; a leaf's place in the list
    is the index its draw is folded from."""
    m = dims(cfg)
    out = [(n, None, _shape(m, n, 0), k) for n, k in TOP]
    for i in range(m["L"]):
        out += [(n, i, _shape(m, n, m["heads"][i]), k)
                for n, k in layer_names(m, i)]
    return out


def window_blocks(cfg: dict) -> int:
    """Pages of the sliding layers' pool as the engine derives them: the
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


def layer_dense_weights(m: dict, i: int) -> int:
    """Elements of layer i's matrices that XLA's dots read: q, the gate
    and o are H x n_i*d, k and v are H x kvh*d; then the dense layer's
    SwiGLU, or the router and the shared expert."""
    H, nh = m["H"], m["heads"][i]
    att = 3 * H * nh * m["d"] + 2 * H * m["kvh"] * m["d"]
    if not m["sparse"][i]:
        return att + 3 * H * m["F"]
    return att + H * m["E"] + 3 * H * m["Fs"]


def step_matmuls(cfg: dict, tokens: int, logit_rows: int, *,
                 bytes_per: int = 2, logit_bytes: int = 4) -> tuple:
    """(operations, bytes) of the products that run as XLA dots in one
    step of ``tokens`` real query tokens and ``logit_rows`` scored rows:
    the q, k, v, gate and output projections, layer 0's SwiGLU, the
    router, the shared expert and the head.  The routed experts are a
    grouped product of their own and are not here
    (``moe.roofline_share``).

    Operations: 2 per weight element per token.  Bytes: those weights
    once a step, per token and layer the activations each product reads
    and writes (the gate's: the attention output in and out again; the
    router's scores float32), per logit row its hidden state in and its
    float32 logits out."""
    m = dims(cfg)
    H, kvh, d, V = m["H"], m["kvh"], m["d"], m["V"]
    w = sum(layer_dense_weights(m, i) for i in range(m["L"]))
    ops = 2 * tokens * w + 2 * logit_rows * H * V
    acts = 0
    for i in range(m["L"]):
        nh = m["heads"][i]
        acts += ((H + (nh + 2 * kvh) * d) + (H + 3 * nh * d)
                 + (nh * d + H)) * bytes_per
        if m["sparse"][i]:
            acts += (H + (H + 2 * m["Fs"]) + (m["Fs"] + H)) * bytes_per \
                + m["E"] * logit_bytes
        else:
            acts += ((H + 2 * m["F"]) + (m["F"] + H)) * bytes_per
    byt = (w + H * V) * bytes_per + tokens * acts \
        + logit_rows * (H * bytes_per + V * logit_bytes)
    return ops, byt


def _row(m: dict, nh: int, n_q: int, kv_len: int, window,
         bytes_per: int) -> tuple:
    """One layer's (operations, bytes) for a row of ``n_q`` queries of
    ``nh`` heads that ends at ``kv_len`` keys; ``window``: the keys a
    query sees, its own among them (None: all up to its own)."""
    kvh, d = m["kvh"], m["d"]
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


def _rows(m: dict, n_q: int, kv_len: int, windowed: bool,
          bytes_per: int) -> tuple:
    """(operations, bytes) of the row over the layers of one kind, each
    at its own head count."""
    ops = byt = 0
    for i in range(m["L"]):
        if m["window"][i] == windowed:
            o, b = _row(m, m["heads"][i], n_q, kv_len,
                        m["W"] if windowed else None, bytes_per)
            ops, byt = ops + o, byt + b
    return ops, byt


def window_attention_row(cfg: dict, n_q: int, kv_len: int, *,
                         bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the SLIDING layers' attention for one row:
    only the pairs and the keys inside the window."""
    return _rows(dims(cfg), n_q, kv_len, True, bytes_per)


def attention_row(cfg: dict, n_q: int, kv_len: int, *,
                  bytes_per: int = 2) -> tuple:
    """(operations, bytes) of attention, all layers, for one row of
    ``n_q`` query tokens that ends at ``kv_len`` keys: the full layers'
    heads see every key up to the query's own, the sliding layers' the
    window's.

    Operations: a multiply-add for q.k and one for p.v, 4 * heads *
    head_dim per (query, key) pair.  Bytes: the K and V rows a layer's
    queries see read once, the new K and V written, q read and the
    output written."""
    m = dims(cfg)
    og, bg = _rows(m, n_q, kv_len, False, bytes_per)
    ow, bw = _rows(m, n_q, kv_len, True, bytes_per)
    return og + ow, bg + bw


def expert_products(cfg: dict, pairs: int, touched: int, *,
                    bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the routed experts' grouped products for
    ``pairs`` token-expert pairs over ``touched`` (layer, expert) pairs
    that got at least one token (both summed over layers, as the engine
    counts them): the products ``[pairs, H] x [H, Fe]`` twice and
    ``[pairs, Fe] x [Fe, H]`` over ``E`` groups.

    Operations: a pair meets its expert's three matrices (gate, up,
    down) once, 2 ops a multiply-add.  Bytes: the three matrices of each
    touched expert once, and per pair the hidden state in, the two
    Fe-wide products out and the gated one in again, and the hidden-wide
    result out."""
    m = dims(cfg)
    per_expert = 3 * m["H"] * m["Fe"]
    ops = 2 * pairs * per_expert
    byt = (touched * per_expert
           + pairs * (m["H"] + 3 * m["Fe"] + m["H"])) * bytes_per
    return ops, byt
