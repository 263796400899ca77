"""Plain reference of a latent-attention decoder whose full layers attend
to a learned selection of their keys and whose sliding-window layers
keep a latent of their own, as ONE chip of an expert-parallel deployment
computes it: straight ``jax.numpy`` in float32 at ``highest`` matmul
precision, the EXPANDED form of the attention (per-head keys and values
made from the latents), the selection as a LITERAL top-k and a mask, the
window as a mask, no kernels, no cache, no batching across requests
(each sequence is one whole causal forward pass).  It imports nothing of
the program and takes nothing the program made: the weights come from
``harness/weights.py`` by the seed (the leaves are those
``shapes/dots3.py`` lists), one layer at a time.

The equations (DeepSeek-V3's latent attention and router, DeepSeek-
V3.2's indexer, which the configuration's keys name).  Layer i, ``h =
RMSNorm(x; ln1)``, eps 1e-5; its kind is ``layer_types[i]``; a sliding
layer reads the ``swa_*`` sizes:

    c_q = RMSNorm(h W_qa; q_a_norm)                 # [T, q_lora_rank]
    q   = c_q W_qb                                  # [T, nh, d_n + d_r]
    [c_kv | k_r] = h W_kva ; c = RMSNorm(c_kv; kv_norm)
    q_r, k_r rotated: pairs (2j, 2j+1) by pos * theta^(-2j/d_r), no scaling
    [k_n | v]_h = W_kvb,h c
    score_h[t, s] = (q_n,h[t] . k_n,h[s] + q_r,h[t] . k_r[s]) (d_n + d_r)^-1/2
    full layer, the indexer:
        qI = c_q WI_qb  as n_I heads of d_I, the first d_r numbers of each rotated
        kI = LayerNorm(h WI_k; ik_norm, ik_bias), its first d_r numbers rotated
        w  = h WI_w                                  # [T, n_I]
        I[t, s] = sum_j w[t, j] n_I^-1/2 d_I^-1/2 relu(qI[t, j] . kI[s])   (s <= t)
        S_t = the min(t + 1, index_topk) positions of largest I[t, .]
              (of equal scores the lower position: ``lax.top_k``'s order)
        a_h[t] = softmax over s in S_t of score_h[t, s], times v_h[s]
    sliding layer:
        a_h[t] = softmax over t - sliding_window_size < s <= t
    g = sigmoid(h W_g)                               # [T, nh]: one number a head
    x = x + concat_h(g_h a_h) W_o
    h2 = RMSNorm(x; ln2)
    layer 0:  x = x + (silu(h2 G) * (h2 U)) D
    others:   s = sigmoid(h2 W_r) over all E experts; idx = the k largest of s + b;
              g = scaling * s_idx / sum(s_idx)
              x = x + shared(h2) + sum over the chosen experts HELD HERE of g_e E_e(h2)

Departures and readings, noted (the configuration's ``assumed`` says the
same):
- the share: this chip holds ``n_routed_experts`` consecutive experts of
  the router's ``router_width`` from ``ep_rank * n_routed_experts`` and a
  slice of the vocabulary; what the absent experts would add is left out
  and that partial result goes on to the next layer (model-configs
  guide, section 4); the program is given the same share;
- ``attention_gate_type`` ``headwise`` names a gate of one number a head
  and not its form: taken as the output gate of the gated-attention
  family at one number a head, from the layer's normed input, on each
  head's output before ``W_o``;
- ``apply_mla_qkv_lora_rescale`` is a fixed scalar on a normed latent,
  which seeded weights of ``W_qb`` / ``W_kvb`` absorb: taken as 1;
- the indexer in float32 here (bfloat16 in the program), without V3.2's
  FP8 and its Hadamard rotation (orthogonal: ``qI . kI`` unchanged);
  ``kI``'s LayerNorm has a learned scale and bias, eps as the model's;
- learned RMSNorms on both latents; no norm on per-head queries or keys;
- ``sliding_window_size`` 513 holds the query's own position: a query at
  t sees s in [t - 512, t];
- rotary pairs are the interleaved (2j, 2j+1) pairs, which is what the
  program computes;
- no multi-token-prediction head, no vision or audio tower: the language
  model alone;
- a sequence is processed as blocks of ``_ROWS`` rows (causal, so
  padding after its end changes nothing a real position sees): a block
  is projected, what it keeps of its positions (the latent, the rope
  key, the index key) is laid into arrays of ``pad_to`` rows, the same
  for every sequence, and the block's queries then attend to those, a
  few queries and a few heads at a time so that a 25k-token pass fits.
  No program depends on a sequence's length: a run compiles seven of
  them whatever lengths its sample holds (a program a length and a kind
  of layer took half a minute each to compile).

``lower="int8"`` is the control's precision: every matrix (each expert's
own) rounded to int8 with one float32 scale per output channel (the
embedding: per row) before use.
"""
from __future__ import annotations

import math

import numpy as np

_ROWS = 2048            # rows a block of a sequence: every program's shape
_Q_BLOCK = 128          # queries a block of the attention and the indexer
_HEADS = 16             # heads a group of the attention


def _int8_round(w, axis):
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _prep(w: dict, lower: str | None) -> dict:
    import jax.numpy as jnp
    out = {}
    for name, a in w.items():
        if lower == "int8" and a.ndim >= 2:
            out[name] = _int8_round(a, axis=-1 if name == "embed" else -2)
        elif lower is None or a.ndim == 1:
            out[name] = a.astype(jnp.float32)
        else:
            raise ValueError(f"no such lower precision: {lower!r}")
    return out


def _rms(x, w, eps):
    import jax.numpy as jnp
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps)) * w


def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def inv_freq(theta: float, d: int) -> np.ndarray:
    """Plain rotary frequencies over ``d`` rotated numbers."""
    return (theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
            ).astype(np.float32)


def _rope(x, inv, first):
    """x [R, heads, d] at positions first .. first + R - 1: its first
    2 * len(inv) numbers rotated in interleaved pairs, the rest
    untouched."""
    import jax.numpy as jnp
    R, r = x.shape[0], 2 * len(inv)
    pos = (first + jnp.arange(R)).astype(jnp.float32)
    ang = pos[:, None, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0:r:2], x[..., 1:r:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                       -1).reshape(x.shape[:-1] + (r,))
    return jnp.concatenate([turned, x[..., r:]], -1)


def _project(x, w, first, *, m, eps, sliding: bool):
    """What the attention needs of rows x [R, H] at positions first ..:
    the query latent, the gate, and what is KEPT of a position for later
    queries: the normed latent ``c``, the rotated rope key and, on a
    full layer, the indexer's key; there also its queries and head
    weights (the score's constant folded in)."""
    import jax
    a = m["kind"][sliding]
    dc, inv = a["dc"], inv_freq(a["theta"], a["dr"])
    h = _rms(x, w["ln1"], eps)
    ckv = h @ w["wkva"]
    out = {"c_q": _rms(h @ w["wqa"], w["q_a_norm"], eps),
           "gate": jax.nn.sigmoid(h @ w["wg"]),
           "c": _rms(ckv[:, :dc], w["kv_norm"], eps),
           "k_rope": _rope(ckv[:, None, dc:], inv, first)[:, 0]}
    if not sliding:
        R, ni, di = x.shape[0], m["ni"], m["di"]
        out["qi"] = _rope((out["c_q"] @ w["wi_q"]).reshape(R, ni, di), inv,
                          first)
        out["ki"] = _rope(_layer_norm(h @ w["wi_k"], w["ik_norm"],
                                      w["ik_bias"], eps)[:, None], inv,
                          first)[:, 0]
        out["wi"] = (h @ w["wi_w"]) * (ni ** -0.5 * di ** -0.5)
    return out


def selection(qi, ki, wi, pos, topk: int):
    """bool [B, Tk]: the keys each of B queries (at positions ``pos``)
    selects: the LITERAL top-k of its index scores over the keys it
    sees."""
    import jax
    import jax.numpy as jnp
    B, Tk = qi.shape[0], ki.shape[0]
    score = jnp.einsum("tj,tjs->ts", wi,
                       jax.nn.relu(jnp.einsum("tjd,sd->tjs", qi, ki)))
    see = jnp.arange(Tk)[None, :] <= pos[:, None]
    score = jnp.where(see, score, -jnp.inf)
    _, idx = jax.lax.top_k(score, min(topk, Tk))
    chosen = jnp.zeros((B, Tk), bool).at[
        jnp.arange(B)[:, None], idx].set(True)
    return chosen & see


def _attend(x, q, keys, w, first, *, m, sliding: bool):
    """x [R, H] plus the attention block's addition, for the queries
    ``q`` (``_project`` of these rows, at positions first ..) against
    the kept rows ``keys`` of positions 0 .. Tk - 1 (rows past a
    sequence's end are padding that no query sees), expanded form: which
    keys each query attends to first, a block of queries at a time, then
    the heads a group at a time."""
    import jax
    import jax.numpy as jnp
    R = x.shape[0]
    a = m["kind"][sliding]
    nh, dn, dr, dv, dc = a["nh"], a["dn"], a["dr"], a["dv"], a["dc"]
    inv = inv_freq(a["theta"], dr)
    Tk = keys["c"].shape[0]
    key = jnp.arange(Tk)
    blocks = jnp.arange(R // _Q_BLOCK)

    def sees(i):
        pos = first + i * _Q_BLOCK + jnp.arange(_Q_BLOCK)
        if sliding:
            return (key[None, :] <= pos[:, None]) \
                & (key[None, :] > pos[:, None] - m["W"])
        cut = lambda t: jax.lax.dynamic_slice_in_dim(
            t, i * _Q_BLOCK, _Q_BLOCK)
        return selection(cut(q["qi"]), keys["ki"], cut(q["wi"]), pos,
                         m["topk"])

    see = jax.lax.map(sees, blocks)                   # [R / B, B, Tk]
    scale = (dn + dr) ** -0.5
    hg = math.gcd(nh, _HEADS)
    wqb = w["wqb"].reshape(-1, nh, dn + dr)
    wkvb = w["wkvb"].reshape(dc, nh, dn + dv)

    def group(g):
        """Heads g*hg .. g*hg+hg-1 over every query of the block."""
        qh = jnp.einsum("tr,rhd->thd", q["c_q"],
                        jax.lax.dynamic_slice_in_dim(wqb, g * hg, hg, axis=1))
        kv = jnp.einsum("tc,chd->thd", keys["c"],
                        jax.lax.dynamic_slice_in_dim(wkvb, g * hg, hg,
                                                     axis=1))
        q_nope, q_rope = qh[..., :dn], _rope(qh[..., dn:], inv, first)
        k_nope, v = kv[..., :dn], kv[..., dn:]

        def block(i):
            qn = jax.lax.dynamic_slice_in_dim(q_nope, i * _Q_BLOCK, _Q_BLOCK)
            qr = jax.lax.dynamic_slice_in_dim(q_rope, i * _Q_BLOCK, _Q_BLOCK)
            s = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
                 + jnp.einsum("qhr,kr->hqk", qr, keys["k_rope"])) * scale
            s = jnp.where(see[i][None], s, -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

        return jax.lax.map(block, blocks).reshape(R, hg, dv)

    att = jnp.moveaxis(jax.lax.map(group, jnp.arange(nh // hg)), 0, 1)
    att = att.reshape(R, nh, dv) * q["gate"][..., None]
    return x + att.reshape(R, nh * dv) @ w["wo"]


def _swiglu(h, gate, up, down):
    import jax
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _experts(h2, w, m):
    """The shared expert, plus the chosen experts held here, for every
    token of h2 [R, H]: each held expert multiplies all tokens and
    counts for those that chose it, with their gate."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(h2 @ w["router"])
    _, idx = jax.lax.top_k(s + w["router_bias"], m["k"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    g = m["scaling"] * picked / jnp.sum(picked, -1, keepdims=True)

    def one(acc, inp):
        e, gate, up, down = inp
        g_e = jnp.sum(jnp.where(idx == e + m["first"], g, 0.0), axis=-1)
        return acc + g_e[:, None] * _swiglu(h2, gate, up, down), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(h2),
        (jnp.arange(m["held"]), w["e_gate"], w["e_up"], w["e_down"]))
    return _swiglu(h2, w["s_gate"], w["s_up"], w["s_down"]) + routed


def _ffn(x, w, *, m, eps, sparse: bool):
    h2 = _rms(x, w["ln2"], eps)
    if sparse:
        return x + _experts(h2, w, m)
    return x + _swiglu(h2, w["gate"], w["up"], w["down"])


def _layer(blocks: list, w: dict, fns, keep: int) -> list:
    """One layer over one sequence, held as blocks of ``_ROWS`` rows:
    project every block (what it keeps of its positions goes into arrays
    of ``keep`` rows, the same for every sequence, so that no program
    depends on a sequence's length), then each block's queries attend
    to the kept rows, then the FFN."""
    import jax.numpy as jnp
    project, put, attend, ffn = fns
    qs, keys = [], None
    for i, x in enumerate(blocks):
        q = project(x, w, i * _ROWS)
        kept = {n: q.pop(n) for n in ("c", "k_rope", "ki") if n in q}
        if keys is None:
            keys = {n: jnp.zeros((keep,) + a.shape[1:], a.dtype)
                    for n, a in kept.items()}
        keys = put(keys, kept, i * _ROWS)
        qs.append(q)
    return [ffn(attend(x, q, keys, w, i * _ROWS), w)
            for i, (x, q) in enumerate(zip(blocks, qs))]


def logits_at(cfg: dict, seed: int, seqs: list, score_from: list,
              n_score: int, pad_to: int, lower: str | None = None):
    """Logits of whole forward passes.

    seqs: token-id lists (prompt then served tokens).  For sequence s the
    rows scored are positions score_from[s] .. score_from[s]+n_score-1
    (clipped to the sequence; rows past its end are padding the caller
    ignores).  Returns float32 [len(seqs), n_score, V] as numpy.
    ``pad_to`` bounds a sequence's length."""
    import functools

    import jax
    import jax.numpy as jnp

    from harness import spec, weights as W

    shapes = spec.load_shapes("dots3")
    m, leaves = shapes.dims(cfg), shapes.leaves(cfg)
    eps = float(cfg["rms_norm_eps"])
    dtype = jnp.dtype(cfg.get("dtype", "bfloat16"))
    for s in seqs:
        if len(s) > pad_to:
            raise ValueError(f"sequence of {len(s)} tokens over {pad_to}")
    keep = -(-pad_to // _ROWS) * _ROWS

    with jax.default_matmul_precision("highest"):
        top = _prep(W.make_top(leaves, seed, dtype), lower)
        embed = jax.jit(lambda e, t: e[t])
        xs = []
        for s in seqs:
            toks = np.zeros((-(-len(s) // _ROWS) * _ROWS,), np.int32)
            toks[:len(s)] = s
            xs.append([embed(top["embed"], jnp.asarray(t))
                       for t in toks.reshape(-1, _ROWS)])

        put = jax.jit(lambda keys, kept, first: {
            n: jax.lax.dynamic_update_slice_in_dim(keys[n], kept[n], first,
                                                   axis=0) for n in keys})

        @functools.cache
        def fns_of(sliding, sparse):
            kw = dict(m=m, sliding=sliding)
            return (jax.jit(functools.partial(_project, eps=eps, **kw)), put,
                    jax.jit(functools.partial(_attend, **kw)),
                    jax.jit(functools.partial(_ffn, m=m, eps=eps,
                                              sparse=sparse)))

        for i in range(m["L"]):
            w = _prep(W.make_layer(leaves, seed, i, dtype), lower)
            fns = fns_of(m["window"][i], m["sparse"][i])
            xs = [_layer(blocks, w, fns, keep) for blocks in xs]
            del w

        head = jax.jit(lambda x, norm_f, head_w:
                       _rms(x, norm_f, eps) @ head_w)
        out = np.zeros((len(seqs), n_score, m["V"]), np.float32)
        for j, (blocks, f) in enumerate(zip(xs, score_from)):
            n = len(blocks) * _ROWS
            rows = np.minimum(np.arange(n_score) + f, n - 1)
            for b in sorted(set(rows // _ROWS)):
                lg = np.asarray(head(blocks[b], top["norm_f"], top["head"]))
                at = rows // _ROWS == b
                out[j, at] = lg[rows[at] % _ROWS]
        return out
