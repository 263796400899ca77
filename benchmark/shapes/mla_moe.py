"""Shapes and counts of a latent-attention (MLA) decoder with sparse
expert layers, as one chip of an expert-parallel deployment serves it
(``references/mla_moe.py`` has the equations).  Imports nothing of the
program.  What every shapes file states is listed in
``shapes/llama_dense.py``; what differs here:

- layers differ: ``first_k_dense_replace`` leading layers have a dense
  SwiGLU, the rest a router over ALL the model's experts, the experts
  held here as three stacked leaves ``[held, in, out]`` and a shared
  expert;
- the cache is one pool for all layers whose page is ``[block, width]``:
  a token's row ``[c | k_rope]`` (576 numbers at the published sizes)
  stored in ``width`` = the next multiple of 128 columns (640), which is
  what an array of 576 columns takes in the TPU's tiled memory anyway;
- the attention kernel runs the absorbed form, so ``attention_row``
  counts that form's operations; the routed experts run as a grouped
  product outside XLA's dots (``moe_experts``: ``metrics/moe.*`` count
  them, ``step_matmuls`` does not)."""
from __future__ import annotations

TOP = (("embed", "embedding"), ("norm_f", "norm"), ("head", "matrix"))
ATTENTION = (("ln1", "norm"), ("wq", "matrix"), ("q_norm", "norm"),
             ("wkva", "matrix"), ("kv_norm", "norm"), ("wkvb", "matrix"),
             ("wo", "matrix"), ("ln2", "norm"))
DENSE = (("gate", "matrix"), ("up", "matrix"), ("down", "matrix"))
EXPERTS = (("router", "matrix"), ("router_bias", "zero"),
           ("e_gate", "matrix"), ("e_up", "matrix"), ("e_down", "matrix"),
           ("s_gate", "matrix"), ("s_up", "matrix"), ("s_down", "matrix"))

KERNELS = ("ragged_latent_attention",)
SCOPES = ("embed", "norm", "q_proj", "kv_latent", "rope", "kv_write",
          "attn", "o_proj", "router", "moe_dispatch", "moe_experts",
          "moe_combine", "shared_expert", "mlp", "head", "sample")
LOOP = "layers"
MATMUL_SCOPES = ("q_proj", "kv_latent", "o_proj", "router",
                 "shared_expert", "mlp", "head")
SAMPLE_SCOPES = ("sample",)
POOL_SCOPES = ("kv_write", "attn")
MOE_SCOPES = ("router", "moe_dispatch", "moe_experts", "moe_combine")
EXPERT_SCOPES = ("moe_experts",)


def dims(cfg: dict) -> dict:
    ep = cfg["expert_parallel"]
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dc = int(cfg["kv_lora_rank"])
    return {"H": int(cfg["hidden_size"]),
            "nh": int(cfg["num_attention_heads"]),
            "dn": dn, "dr": dr, "dq": dn + dr, "dv": int(cfg["v_head_dim"]),
            "dc": dc, "row": dc + dr,
            "width": -(-(dc + dr) // 128) * 128,
            "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "held": int(cfg["num_experts"]),
            "E": int(ep["router_width"]), "first": int(ep["ep_rank"])
            * int(cfg["num_experts"]),
            "k": int(cfg["num_experts_per_tok"]),
            "shared": int(cfg["num_shared_experts"]),
            "dense": int(cfg["first_k_dense_replace"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"])}


def _shape(m: dict, name: str) -> tuple:
    H, nh, dq, dc, dr = m["H"], m["nh"], m["dq"], m["dc"], m["dr"]
    Fs = m["Fe"] * m["shared"]
    return {"ln1": (H,), "ln2": (H,), "norm_f": (H,), "q_norm": (dq,),
            "kv_norm": (dc,), "wq": (H, nh * dq), "wkva": (H, dc + dr),
            "wkvb": (dc, nh * (m["dn"] + m["dv"])),
            "wo": (nh * m["dv"], H),
            "gate": (H, m["F"]), "up": (H, m["F"]), "down": (m["F"], H),
            "router": (H, m["E"]), "router_bias": (m["E"],),
            "e_gate": (m["held"], H, m["Fe"]),
            "e_up": (m["held"], H, m["Fe"]),
            "e_down": (m["held"], m["Fe"], H),
            "s_gate": (H, Fs), "s_up": (H, Fs), "s_down": (Fs, H),
            "embed": (m["V"], H), "head": (H, m["V"])}[name]


def layer_names(m: dict, i: int) -> tuple:
    return ATTENTION + (DENSE if i < m["dense"] else EXPERTS)


def leaves(cfg: dict) -> list:
    """[(name, layer or None, shape, kind)]; a leaf's place in the list
    is the index its draw is folded from."""
    m = dims(cfg)
    out = [(n, None, _shape(m, n), k) for n, k in TOP]
    for i in range(m["L"]):
        out += [(n, i, _shape(m, n), k) for n, k in layer_names(m, i)]
    return out


def pool_shapes(cfg: dict) -> set:
    """Dimension lists of the latent pool and of one layer of it:
    [L, num_blocks, block, width], [num_blocks, block, width] (also with
    a leading 1)."""
    s, m = cfg["serving"], dims(cfg)
    one = [int(s["num_blocks"]), int(s["block_size"]), m["width"]]
    return {tuple([m["L"]] + one), tuple(one), tuple([1] + one)}


def _layer_dense_weights(m: dict, i: int) -> int:
    """Elements of layer i's matrices that XLA's dots read: everything
    but the routed experts."""
    H, nh = m["H"], m["nh"]
    att = H * nh * m["dq"] + H * m["row"] \
        + m["dc"] * nh * (m["dn"] + m["dv"]) + nh * m["dv"] * H
    if i < m["dense"]:
        return att + 3 * H * m["F"]
    return att + H * m["E"] + 3 * H * m["Fe"] * m["shared"]


def step_matmuls(cfg: dict, tokens: int, logit_rows: int, *,
                 bytes_per: int = 2, logit_bytes: int = 4) -> tuple:
    """(operations, bytes) of the products that run as XLA dots in one
    step of ``tokens`` real query tokens and ``logit_rows`` scored rows:
    the query, latent and output projections, the two absorbed products
    (``W_kvb``'s key half into the query, its value half out of the
    weighted latents), the dense layers' SwiGLU, the router, the shared
    experts and the head.  The routed experts are a grouped product of
    their own and are not here (``moe.roofline_share``).

    Operations: 2 per weight element per token (each half of ``W_kvb``
    meets every token once).  Bytes: those weights once a step, per
    token and layer the activations each product reads and writes, per
    logit row its hidden state in and its float32 logits out."""
    m = dims(cfg)
    H, nh, V = m["H"], m["nh"], m["V"]
    w = sum(_layer_dense_weights(m, i) for i in range(m["L"]))
    ops = 2 * tokens * w + 2 * logit_rows * H * V
    att = (H + nh * m["dq"]) + (H + m["row"]) \
        + nh * (m["dn"] + m["dc"]) + nh * (m["dc"] + m["dv"]) \
        + (nh * m["dv"] + H)
    dense = (H + 2 * m["F"]) + (m["F"] + H)
    Fs = m["Fe"] * m["shared"]
    sparse = (H + m["E"]) + (H + 2 * Fs) + (Fs + H)
    acts = att * m["L"] + dense * m["dense"] \
        + sparse * (m["L"] - m["dense"])
    byt = (w + H * V) * bytes_per + tokens * acts * bytes_per \
        + logit_rows * (H * bytes_per + V * logit_bytes)
    return ops, byt


def attention_row(cfg: dict, n_q: int, kv_len: int, *,
                  bytes_per: int = 2) -> tuple:
    """(operations, bytes) of causal latent attention in the absorbed
    form, all layers, for one row of ``n_q`` query tokens that ends at
    ``kv_len`` keys, at the row's real length.

    Operations: per (query, key) pair and head a score over the cached
    row (latent and rope columns) and a weighted sum of its latent
    columns, 2 ops a multiply-add.  Bytes: the row's cached rows read
    once, the new rows written, the absorbed queries read and the
    weighted latents written."""
    m = dims(cfg)
    pairs = n_q * kv_len - n_q * (n_q - 1) // 2
    ops = 2 * m["nh"] * (m["row"] + m["dc"]) * pairs
    byt = (kv_len * m["row"] + n_q * m["row"]
           + n_q * m["nh"] * (m["row"] + m["dc"])) * bytes_per
    return ops * m["L"], byt * m["L"]


def expert_products(cfg: dict, pairs: int, touched: int, *,
                    bytes_per: int = 2) -> tuple:
    """(operations, bytes) of the routed experts' grouped products for
    ``pairs`` token-expert pairs computed here over ``touched`` (layer,
    expert) matrices triples that got at least one token (both summed
    over layers, as the engine counts them).

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
