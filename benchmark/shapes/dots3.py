"""Shapes and counts of a latent-attention decoder whose full layers
attend to a learned selection of their keys (an indexer in front of the
attention) and whose sliding-window layers keep a latent of their own,
with a headwise gate on the attention output, a leading dense layer and
routed experts beside a shared one, as one chip of an expert-parallel
deployment serves it (``references/dots3.py`` has the equations).
Imports nothing of the program.  What every shapes file states is listed
in ``shapes/llama_dense.py``; what differs here:

- layers are of two kinds by ``layer_types`` (its first
  ``num_hidden_layers`` entries); a kind has its own heads, ranks and
  head sizes (the ``swa_*`` keys are the sliding layers'), so every
  attention leaf of a full layer and of a sliding layer differs in
  shape, and so do their cached rows: ``[c 512 | k_rope 64]`` stored 640
  wide in the full layers' pool ``[Lg, num_blocks, block, 640]``, ``[c
  1024 | k_rope 64]`` stored 1152 wide in the sliding layers' ``[Lw, Nw,
  block, 1152]``, whose size the ENGINE derives (``window_blocks``).  A
  THIRD array lies beside the full layers' pool under the same table and
  page ids: the indexer's keys ``[Lg, num_blocks, block, 128]``;
- ``attention_row`` counts a full layer's heads over the (query, key)
  pairs INSIDE the selection (a query at position p attends to
  ``min(p + 1, index_topk)`` keys) and a sliding layer's over the pairs
  inside the window, in the absorbed form, whatever the kernels walk:
  the counts are of the semantics.  ``window_attention_row`` is the
  sliding layers' part alone; ``WINDOW_KERNELS`` names their launches;
- ``index_row`` counts the indexer's score products: every (query, key)
  pair a query SEES, ``index_n_heads`` heads of ``index_head_dim``
  (``metrics/attn.index_roofline_share.py``); its projections, the score
  kernel and the sum over heads run under ``attn_index``
  (``INDEX_SCOPES``), the selection under ``attn_select``
  (``SELECT_SCOPES``);
- the gate's product ``h W_g`` and its multiply run under ``attn_gate``;
  it, the indexer's three projections, the low-rank query's two, layer
  0's SwiGLU, the router and the shared expert count with the matmuls;
  the routed experts run as a grouped product outside XLA's dots
  (``moe_experts``: ``metrics/moe.*`` count them through
  ``expert_products``; ``step_matmuls`` does not)."""
from __future__ import annotations

TOP = (("embed", "embedding"), ("norm_f", "norm"), ("head", "matrix"))
ATTENTION = (("ln1", "norm"), ("wqa", "matrix"), ("q_a_norm", "norm"),
             ("wqb", "matrix"), ("wkva", "matrix"), ("kv_norm", "norm"),
             ("wkvb", "matrix"), ("wo", "matrix"), ("wg", "matrix"))
INDEX = (("wi_q", "matrix"), ("wi_k", "matrix"), ("ik_norm", "norm"),
         ("ik_bias", "zero"), ("wi_w", "matrix"))
DENSE = (("gate", "matrix"), ("up", "matrix"), ("down", "matrix"))
EXPERTS = (("router", "matrix"), ("router_bias", "zero"),
           ("e_gate", "matrix"), ("e_up", "matrix"), ("e_down", "matrix"),
           ("s_gate", "matrix"), ("s_up", "matrix"), ("s_down", "matrix"))

WINDOW_KERNELS = ("ragged_latent_attention_window",)
KERNELS = ("ragged_latent_attention_selected",) + WINDOW_KERNELS
INDEX_KERNELS = ("ragged_index_scores",)
SCOPES = ("embed", "norm", "q_proj", "kv_latent", "rope", "kv_write",
          "attn_index", "attn_select", "attn", "attn_window", "attn_gate",
          "o_proj", "router", "moe_dispatch", "moe_experts", "moe_combine",
          "shared_expert", "mlp", "head", "sample")
LOOP = "layers"
MATMUL_SCOPES = ("q_proj", "kv_latent", "attn_gate", "o_proj", "router",
                 "shared_expert", "mlp", "head")
SAMPLE_SCOPES = ("sample",)
POOL_SCOPES = ("kv_write", "attn", "attn_window", "attn_index")
MOE_SCOPES = ("router", "moe_dispatch", "moe_experts", "moe_combine")
EXPERT_SCOPES = ("moe_experts",)
GATE_SCOPES = ("attn_gate",)
INDEX_SCOPES = ("attn_index",)
SELECT_SCOPES = ("attn_select",)


def _kind(cfg: dict, sliding: bool) -> dict:
    """One kind of layer's attention sizes (``swa_*`` keys: sliding)."""
    pre = "swa_" if sliding else ""
    dn = int(cfg[pre + "qk_nope_head_dim"])
    dr = int(cfg[pre + "qk_rope_head_dim"])
    dc = int(cfg[pre + "kv_lora_rank"])
    return {"nh": int(cfg[pre + "num_attention_heads"]),
            "rq": int(cfg[pre + "q_lora_rank"]), "dc": dc, "dn": dn,
            "dr": dr, "dv": int(cfg[pre + "v_head_dim"]),
            "row": dc + dr, "width": -(-(dc + dr) // 128) * 128,
            "theta": float(cfg[pre + "rope_theta"])}


def dims(cfg: dict) -> dict:
    ep = cfg["expert_parallel"]
    L = int(cfg["num_hidden_layers"])
    window = [t == "sliding_attention" for t in cfg["layer_types"][:L]]
    if len(window) != L:
        raise ValueError(f"layer_types holds {len(window)} entries for "
                         f"{L} layers")
    for key in ("attention_gate_type", "swa_attention_gate_type"):
        if cfg.get(key) != "headwise":
            raise ValueError(f"the leaves below hold a headwise gate: "
                             f"{key} is {cfg.get(key)!r}")
    dense = int(cfg["first_k_dense_replace"])
    return {"H": int(cfg["hidden_size"]),
            "kind": {False: _kind(cfg, False), True: _kind(cfg, True)},
            "ni": int(cfg["index_n_heads"]), "di": int(cfg["index_head_dim"]),
            "topk": int(cfg["index_topk"]),
            "W": int(cfg["sliding_window_size"]),
            "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "held": int(cfg["n_routed_experts"]),
            "E": int(ep["router_width"]),
            "first": int(ep["ep_rank"]) * int(cfg["n_routed_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg["n_shared_experts"]),
            "scaling": float(cfg["routed_scaling_factor"]),
            "dense": dense, "sparse": [i >= dense for i in range(L)],
            "window": window, "Lw": sum(window), "Lg": L - sum(window),
            "V": int(cfg["vocab_size"]), "L": L}


def _shape(m: dict, name: str, a: dict) -> tuple:
    H, F, Fe, E = m["H"], m["F"], m["Fe"], m["E"]
    Fs = Fe * m["shared"]
    nh, rq, dc, dn, dr, dv = (a[k] for k in ("nh", "rq", "dc", "dn", "dr",
                                             "dv"))
    return {"ln1": (H,), "ln2": (H,), "norm_f": (H,),
            "wqa": (H, rq), "q_a_norm": (rq,),
            "wqb": (rq, nh * (dn + dr)), "wkva": (H, dc + dr),
            "kv_norm": (dc,), "wkvb": (dc, nh * (dn + dv)),
            "wo": (nh * dv, H), "wg": (H, nh),
            "wi_q": (rq, m["ni"] * m["di"]), "wi_k": (H, m["di"]),
            "ik_norm": (m["di"],), "ik_bias": (m["di"],),
            "wi_w": (H, m["ni"]),
            "gate": (H, F), "up": (H, F), "down": (F, H),
            "router": (H, E), "router_bias": (E,),
            "e_gate": (m["held"], H, Fe), "e_up": (m["held"], H, Fe),
            "e_down": (m["held"], Fe, H),
            "s_gate": (H, Fs), "s_up": (H, Fs), "s_down": (Fs, H),
            "embed": (m["V"], H), "head": (H, m["V"])}[name]


def layer_names(m: dict, i: int) -> tuple:
    """Layer i's leaves in the order the program's model lists them."""
    return ATTENTION + (() if m["window"][i] else INDEX) \
        + (("ln2", "norm"),) + (EXPERTS if m["sparse"][i] else DENSE)


def leaves(cfg: dict) -> list:
    """[(name, layer or None, shape, kind)]; a leaf's place in the list
    is the index its draw is folded from."""
    m = dims(cfg)
    out = [(n, None, _shape(m, n, m["kind"][False]), k) for n, k in TOP]
    for i in range(m["L"]):
        a = m["kind"][m["window"][i]]
        out += [(n, i, _shape(m, n, a), k) for n, k in layer_names(m, i)]
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
    """Dimension lists of the three pools and of one layer of each: the
    full layers' latents [Lg, num_blocks, block, 640], their index keys
    [Lg, num_blocks, block, 128] and the sliding layers' latents [Lw,
    Nw, block, 1152], each also without its layer axis and with a
    leading 1."""
    s, m = cfg["serving"], dims(cfg)
    bs = int(s["block_size"])
    out = set()
    for layers, pages, width in (
            (m["Lg"], int(s["num_blocks"]), m["kind"][False]["width"]),
            (m["Lg"], int(s["num_blocks"]), m["di"]),
            (m["Lw"], window_blocks(cfg), m["kind"][True]["width"])):
        one = [pages, bs, width]
        out |= {tuple([layers] + one), tuple(one), tuple([1] + one)}
    return out


def layer_dense_weights(m: dict, i: int) -> int:
    """Elements of layer i's matrices that XLA's dots read: everything
    but the routed experts (the indexer's three projections among
    them)."""
    H, a = m["H"], m["kind"][m["window"][i]]
    att = H * a["rq"] + a["rq"] * a["nh"] * (a["dn"] + a["dr"]) \
        + H * a["row"] + a["dc"] * a["nh"] * (a["dn"] + a["dv"]) \
        + a["nh"] * a["dv"] * H + H * a["nh"]
    if not m["window"][i]:
        att += a["rq"] * m["ni"] * m["di"] + H * m["di"] + H * m["ni"]
    if not m["sparse"][i]:
        return att + 3 * H * m["F"]
    return att + H * m["E"] + 3 * H * m["Fe"] * m["shared"]


def step_matmuls(cfg: dict, tokens: int, logit_rows: int, *,
                 bytes_per: int = 2, logit_bytes: int = 4) -> tuple:
    """(operations, bytes) of the products that run as XLA dots in one
    step of ``tokens`` real query tokens and ``logit_rows`` scored rows:
    the low-rank query's two products, the latent projection, the two
    absorbed products (``W_kvb``'s key half into the query, its value
    half out of the weighted latents), the gate, the output projection,
    a full layer's three index projections, layer 0's SwiGLU, the
    router, the shared expert and the head.  The routed experts are a
    grouped product of their own and the index scores a kernel of their
    own: neither is here.

    Operations: 2 per weight element per token.  Bytes: those weights
    once a step, per token and layer the activations each product reads
    and writes, per logit row its hidden state in and its float32
    logits out."""
    m = dims(cfg)
    H, V = m["H"], m["V"]
    w = sum(layer_dense_weights(m, i) for i in range(m["L"]))
    ops = 2 * tokens * w + 2 * logit_rows * H * V
    acts = 0
    Fs = m["Fe"] * m["shared"]
    for i in range(m["L"]):
        a = m["kind"][m["window"][i]]
        nh = a["nh"]
        acts += ((H + a["rq"]) + (a["rq"] + nh * (a["dn"] + a["dr"]))
                 + (H + a["row"]) + nh * (a["dn"] + a["dc"])
                 + nh * (a["dc"] + a["dv"]) + (H + nh)
                 + (nh * a["dv"] + H)) * bytes_per
        if not m["window"][i]:
            acts += ((a["rq"] + m["ni"] * m["di"]) + (H + m["di"])
                     + (H + m["ni"])) * bytes_per
        if m["sparse"][i]:
            acts += ((H + (H + 2 * Fs) + (Fs + H)) * bytes_per
                     + m["E"] * logit_bytes)
        else:
            acts += ((H + 2 * m["F"]) + (m["F"] + H)) * bytes_per
    byt = (w + H * V) * bytes_per + tokens * acts \
        + logit_rows * (H * bytes_per + V * logit_bytes)
    return ops, byt


def _pairs(n_q: int, kv_len: int, most) -> tuple:
    """((query, key) pairs, keys read) of a row of ``n_q`` queries that
    ends at ``kv_len`` keys when a query at position p attends to
    ``min(p + 1, most)`` keys (None: all it sees).  The keys read are at
    most those the row's queries see."""
    first = kv_len - n_q
    if most is None or kv_len <= most:
        return n_q * kv_len - n_q * (n_q - 1) // 2, kv_len
    short = max(0, min(n_q, most - 1 - first))         # p + 1 < most
    pairs = short * (first + 1) + short * (short - 1) // 2 \
        + (n_q - short) * most
    return pairs, kv_len


def _latent_row(a: dict, n_q: int, pairs: int, keys: int,
                bytes_per: int) -> tuple:
    """One layer's (operations, bytes), absorbed form: per pair and head
    a score over the cached row and a weighted sum of its latent
    columns; the keys' rows read once, the new rows written, the
    absorbed queries read and the weighted latents written."""
    ops = 2 * a["nh"] * (a["row"] + a["dc"]) * pairs
    byt = (keys * a["row"] + n_q * a["row"]
           + n_q * a["nh"] * (a["row"] + a["dc"])) * bytes_per
    return ops, byt


def window_attention_row(cfg: dict, n_q: int, kv_len: int, *,
                         bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the SLIDING layers' attention for one row:
    only the pairs and the keys inside the window."""
    m = dims(cfg)
    pairs, _ = _pairs(n_q, kv_len, m["W"])
    keys = kv_len - max(0, kv_len - n_q - m["W"] + 1)
    o, b = _latent_row(m["kind"][True], n_q, pairs, keys, bytes_per)
    return o * m["Lw"], b * m["Lw"]


def attention_row(cfg: dict, n_q: int, kv_len: int, *,
                  bytes_per: int = 2) -> tuple:
    """(operations, bytes) of attention, all layers, for one row of
    ``n_q`` query tokens that ends at ``kv_len`` keys: a full layer's
    heads over the pairs inside the selection (a query attends to at
    most ``index_topk`` keys; a query's selected rows are read once a
    query where the row is longer than the selection, else the row's
    keys once), a sliding layer's over the pairs inside the window."""
    m = dims(cfg)
    a = m["kind"][False]
    pairs, keys = _pairs(n_q, kv_len, m["topk"])
    og, bg = _latent_row(a, n_q, pairs, min(keys, pairs), bytes_per)
    ow, bw = window_attention_row(cfg, n_q, kv_len, bytes_per=bytes_per)
    return og * m["Lg"] + ow, bg * m["Lg"] + bw


def index_row(cfg: dict, n_q: int, kv_len: int, *,
              bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the indexer's score products, the full
    layers', for one row: every (query, key) pair a query sees,
    ``index_n_heads`` heads of ``index_head_dim``, a multiply-add each;
    the row's index keys read once, the new keys written, the index
    queries and the heads' float32 weights read.  Counted from the rows
    and their lengths, whatever implements the product."""
    m = dims(cfg)
    pairs, keys = _pairs(n_q, kv_len, None)
    ops = 2 * m["ni"] * m["di"] * pairs
    byt = (keys + n_q) * m["di"] * bytes_per \
        + n_q * m["ni"] * (m["di"] * bytes_per + 4)
    return ops * m["Lg"], byt * m["Lg"]


def selected_pairs(cfg: dict, n_q: int, kv_len: int) -> tuple:
    """((query, key) pairs a full layer's queries select, pairs they
    see) for one row: what the engine's ``index_keys_selected`` and
    ``index_keys_visible`` count."""
    m = dims(cfg)
    return _pairs(n_q, kv_len, m["topk"])[0], _pairs(n_q, kv_len, None)[0]


def expert_products(cfg: dict, pairs: int, touched: int, *,
                    bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the routed experts' grouped products for
    ``pairs`` token-expert pairs computed here over ``touched`` (layer,
    expert) matrix triples that got at least one token (both summed over
    layers, as the engine counts them).

    Operations: a pair meets its expert's three matrices once, 2 ops a
    multiply-add.  Bytes: the three matrices of each touched expert
    once, and per pair the hidden state in, the two F-wide products out
    and in again, and the hidden-wide result out."""
    m = dims(cfg)
    per_expert = 3 * m["H"] * m["Fe"]
    ops = 2 * pairs * per_expert
    byt = (touched * per_expert
           + pairs * (m["H"] + 3 * m["Fe"] + m["H"])) * bytes_per
    return ops, byt
