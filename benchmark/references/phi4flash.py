"""Plain reference of a decoder-hybrid-decoder (``model_type``
``phi4flash``, the SambaY architecture of arXiv:2507.06607): straight
``jax.numpy`` in float32 at ``highest`` matmul precision, a ``lax.scan``
for the recurrence, no kernel, no cache, no batching across requests
(each sequence is one whole forward pass), windows as MASKS on full
score rows.  It imports nothing of the program and takes nothing the
program made: the weights come from ``harness/weights.py`` by the seed
(the leaves are those ``shapes/phi4flash.py`` lists, a run of the stack
at a time, mapped by its ``published``).

``x`` is ``[T, H]``; ``LN`` is LayerNorm with weight and bias.  Layer
``i`` of ``L``: ``x += Mix_i(LN1_i(x))``; ``x += (silu(g) * u) W_down``
with ``g = LN2_i(x) W_gate``, ``u = LN2_i(x) W_up``.  ``Mix_i``, ``h``
its normed input:

    i even, i <= L/2 (Mamba-1):
        [u | z] = h W_in;  u = silu(conv(u))   # causal, depthwise, taps + bias
        [r | B | C] = u W_x;  delta = softplus(r W_dt + b_dt);  A = -exp(A_log)
        s_t = exp(delta_t A) s_{t-1} + (delta_t u_t) (x) B_t;  y_t = s_t C_t + D u_t
        Mix = (y * silu(z)) W_out;   layer L/2 also keeps m = y
    i even, i > L/2 (gated memory unit):
        Mix = (m * silu(h W_in)) W_out
    i odd, i <= L/2 + 1 (differential attention; a window of W below L/2):
        [q | k | v] = h W_qkv + b;  pair n < nh/2, j = n // (nh/kvh):
        a1_n = softmax(q_{2n} k_{2j}^T / sqrt(hd)) [v_{2j} | v_{2j+1}]
        a2_n = softmax(q_{2n+1} k_{2j+1}^T / sqrt(hd)) [v_{2j} | v_{2j+1}]
        lambda = exp(lq1.lk1) - exp(lq2.lk2) + l0,  l0 = 0.8 - 0.6 exp(-0.3 i)
        o_n = (1 - l0) RMSNorm(a1_n - lambda a2_n; subln);  Mix = [o_n] W_o + b_o
    i odd, i > L/2 + 1 (cross attention):
        q = h W_q + b alone; k, v are layer L/2 + 1's; the same form
    logits = LN_f(x) E^T                                  # the head is tied

Readings, noted (the configuration's ``assumed`` says the same): Mamba-1
at its defaults; differential attention with the paper's ``l0``; the
FIRST half of ``fc1`` is the gate; biases on ``Wqkv`` / ``out_proj``;
``m`` is taken before the ``z`` gate and includes ``D u``; a window of W
holds the query's own position; value heads 2j and 2j+1 side by side
are pair-group j's 128-wide value.

So that no program depends on a length, a sequence is processed in
blocks of ``_BLOCK`` rows (the recurrence and the convolution carried
from block to block, attention a block of queries against ``pad_to``
keys under a mask) and scored ``_SCORE`` rows at a time.

``lower="int8"`` is the control's precision: every matrix rounded to
int8 with one float32 scale per output channel (the embedding: per row)
before use.
"""
from __future__ import annotations

import numpy as np

_BLOCK = 1024           # rows a block
_SCORE = 256            # rows a block of the head


def _int8_round(w, axis):
    import jax.numpy as jnp
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


_VECTORS = ("conv_w", "A_log")      # two axes, and no matrix of a product


def _prep(w: dict, lower):
    """One layer's (or the top's) leaves in float32; with ``lower`` every
    matrix rounded."""
    import jax.numpy as jnp
    out = {}
    for name, a in w.items():
        if lower == "int8" and a.ndim == 2 and name not in _VECTORS:
            out[name] = _int8_round(a, axis=-1 if name == "embed" else -2)
        elif lower in (None, "int8"):
            out[name] = a.astype(jnp.float32)
        else:
            raise ValueError(f"no such lower precision: {lower!r}")
    return out


def _ln(x, w, b, eps):
    import jax.numpy as jnp
    mu = jnp.mean(x, -1, keepdims=True)
    xc = x - mu
    return xc / jnp.sqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps) * w + b


def _ffn(x, w, eps):
    import jax
    h2 = _ln(x, w["ln2"], w["ln2_b"], eps)
    return x + (jax.nn.silu(h2 @ w["gate"]) * (h2 @ w["up"])) @ w["down"]


def _ssm_block(x, tail, s, w, *, m):
    """A block of a Mamba layer: (x out, the scan's output y, the
    convolution's last inputs, the state) from the block's rows, the
    ``taps - 1`` inputs before them and the state before them."""
    import jax
    import jax.numpy as jnp
    di, N, R, taps = m["di"], m["N"], m["R"], m["taps"]
    T = x.shape[0]
    h = _ln(x, w["ln1"], w["ln1_b"], m["eps"])
    uz = h @ w["w_in"]
    u, z = uz[:, :di], uz[:, di:]
    ext = jnp.concatenate([tail, u])
    u = jax.nn.silu(sum(w["conv_w"][j] * ext[j:j + T] for j in range(taps))
                    + w["conv_b"])
    rbc = u @ w["w_x"]
    r, Bm, Cm = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    delta = jax.nn.softplus(r @ w["w_dt"] + w["b_dt"])
    A = -jnp.exp(w["A_log"])                              # [N, di]

    def token(s, inp):
        u_t, d_t, b_t, c_t = inp
        s = jnp.exp(d_t[None, :] * A) * s \
            + (d_t * u_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0) + w["D"] * u_t

    s, y = jax.lax.scan(token, s, (u, delta, Bm, Cm))
    x = x + (y * jax.nn.silu(z)) @ w["w_out"]
    return _ffn(x, w, m["eps"]), y, ext[T:], s


def _gmu_block(x, mem, w, *, m):
    import jax
    h = _ln(x, w["ln1"], w["ln1_b"], m["eps"])
    x = x + (mem * jax.nn.silu(h @ w["w_in"])) @ w["w_out"]
    return _ffn(x, w, m["eps"])


def _attend(q, k, v, first, w, depth, *, m, window):
    """Differential attention of a block of queries (the first at
    position ``first``) over ``k``, ``v`` [S, kvh, hd], masked."""
    import jax
    import jax.numpy as jnp
    nh, kvh, hd = m["nh"], m["kvh"], m["hd"]
    T, S = q.shape[0], k.shape[0]
    g = nh // kvh                          # head pairs a key/value pair
    pos = first + jnp.arange(T)
    key = jnp.arange(S)
    see = key[None, :] <= pos[:, None]
    if window:
        see &= key[None, :] > pos[:, None] - m["W"]
    # [T, kvh/2 (j), g (pair n of j), 2 (which map), hd]
    q = q.reshape(T, kvh // 2, g, 2, hd)
    k = k.reshape(S, kvh // 2, 2, hd)
    v = v.reshape(S, kvh // 2, 2 * hd)
    sc = jnp.einsum("tjgcd,sjcd->jgcts", q, k) / np.sqrt(hd)
    sc = jnp.where(see[None, None, None], sc, -jnp.inf)
    a = jnp.einsum("jgcts,sje->tjgce", jax.nn.softmax(sc, axis=-1), v)
    l0 = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = jnp.exp(jnp.sum(w["lq1"] * w["lk1"])) \
        - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + l0
    o = a[:, :, :, 0] - lam * a[:, :, :, 1]               # [T, j, g, 2 hd]
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + m["eps"]) \
        * w["subln"]
    return ((1.0 - l0) * o).reshape(T, nh * hd) @ w["wo"] + w["bo"]


def _attn_block(x, K, V, first, w, *, m, depth, window):
    """A block of an attention layer that makes its own keys and values:
    they go into ``K``, ``V`` [pad, kvh, hd] at ``first``."""
    import jax
    nh, kvh, hd = m["nh"], m["kvh"], m["hd"]
    T = x.shape[0]
    h = _ln(x, w["ln1"], w["ln1_b"], m["eps"])
    qkv = h @ w["wqkv"] + w["bqkv"]
    q = qkv[:, :nh * hd].reshape(T, nh, hd)
    k = qkv[:, nh * hd:(nh + kvh) * hd].reshape(T, kvh, hd)
    v = qkv[:, (nh + kvh) * hd:].reshape(T, kvh, hd)
    K = jax.lax.dynamic_update_slice_in_dim(K, k, first, 0)
    V = jax.lax.dynamic_update_slice_in_dim(V, v, first, 0)
    x = x + _attend(q, K, V, first, w, depth, m=m, window=window)
    return _ffn(x, w, m["eps"]), K, V


def _cross_block(x, K, V, first, depth, w, *, m):
    nh, hd = m["nh"], m["hd"]
    h = _ln(x, w["ln1"], w["ln1_b"], m["eps"])
    q = (h @ w["wq"] + w["bq"]).reshape(x.shape[0], nh, hd)
    x = x + _attend(q, K, V, first, w, depth, m=m, window=False)
    return _ffn(x, w, m["eps"])


def logits_at(cfg: dict, seed: int, seqs: list, score_from: list,
              n_score: int, pad_to: int, lower: str | None = None):
    """Logits of whole forward passes.

    seqs: token-id lists (prompt then served tokens).  For sequence s the
    rows scored are positions score_from[s] .. (clipped to the sequence;
    rows past its end are left zero: padding the caller ignores).
    Returns float32 [len(seqs), rows, V] as numpy, ``rows`` = ``n_score``
    or, if a sequence has more rows to score, that many.  ``pad_to``
    bounds a sequence's length."""
    import functools

    import jax
    import jax.numpy as jnp

    from harness import spec, weights as W

    shapes = spec.load_shapes("phi4flash")
    m, leaves = shapes.dims(cfg), shapes.leaves(cfg)
    dtype = jnp.dtype(cfg.get("dtype", "bfloat16"))
    pad = -(-int(pad_to) // _BLOCK) * _BLOCK
    for s in seqs:
        if len(s) > pad_to:
            raise ValueError(f"sequence of {len(s)} tokens over {pad_to}")
    blocks = [-(-len(s) // _BLOCK) for s in seqs]

    def run_of(tag):
        """{k: {leaf: array}} of one run of the stack, as drawn and
        mapped (a repeating run's leaves still stacked)."""
        out = {}
        for tagged, a in W.make_layer(leaves, seed, tag, dtype).items():
            k, name = tagged.split(".", 1)
            out.setdefault(int(k), {})[name] = shapes.published(name, a)
        return out

    with jax.default_matmul_precision("highest"):
        top = _prep({name: shapes.published(name, a) for name, a
                     in W.make_top(leaves, seed, dtype).items()}, lower)
        embed = jax.jit(lambda e, t: e[t])
        # x[s][b]: block b of sequence s
        xs = []
        for s, nb in zip(seqs, blocks):
            toks = np.zeros((nb * _BLOCK,), np.int32)
            toks[:len(s)] = s
            x = embed(top["embed"], jnp.asarray(toks))
            xs.append([x[b * _BLOCK:(b + 1) * _BLOCK] for b in range(nb)])
        ssm = jax.jit(functools.partial(_ssm_block, m=m))
        gmu = jax.jit(functools.partial(_gmu_block, m=m))
        attn = {win: jax.jit(functools.partial(_attn_block, m=m,
                                               window=win),
                             static_argnames=()) for win in (True, False)}
        cross = jax.jit(functools.partial(_cross_block, m=m))
        prep = jax.jit(lambda w: _prep(w, lower))
        kv_shape = (pad, m["kvh"], m["hd"])
        mems = [None] * len(seqs)          # layer L/2's y, by block
        kvs = [None] * len(seqs)           # layer L/2 + 1's K and V
        held, run = None, None
        for i in range(m["L"]):
            tag, k, rep = shapes.layer_of(m, i)
            if tag != held:
                held, run = tag, run_of(tag)
            n = shapes.runs(m)[tag][1]
            w = prep({name: a[rep] if n > 1 else a
                      for name, a in run[k].items()})
            kind = shapes.kind_of(m, i)
            depth = jnp.float32(i)
            for j, nb in enumerate(blocks):
                if kind in ("ssm", "ssm_keep"):
                    tail = jnp.zeros((m["taps"] - 1, m["di"]), jnp.float32)
                    s = jnp.zeros((m["N"], m["di"]), jnp.float32)
                    ys = []
                    for b in range(nb):
                        xs[j][b], y, tail, s = ssm(xs[j][b], tail, s, w)
                        ys.append(y)
                    if kind == "ssm_keep":
                        mems[j] = ys
                elif kind == "gmu":
                    for b in range(nb):
                        xs[j][b] = gmu(xs[j][b], mems[j][b], w)
                elif kind == "diff_cross":
                    for b in range(nb):
                        xs[j][b] = cross(xs[j][b], *kvs[j],
                                         jnp.int32(b * _BLOCK), depth, w)
                else:
                    K = jnp.zeros(kv_shape, jnp.float32)
                    V = jnp.zeros(kv_shape, jnp.float32)
                    for b in range(nb):
                        xs[j][b], K, V = attn[kind == "diff_window"](
                            xs[j][b], K, V, jnp.int32(b * _BLOCK), w,
                            depth=depth)
                    if kind == "diff":
                        kvs[j] = (K, V)
            del w
        del run

        def head(xa, xb, rows, norm_f, norm_f_b, embed_w):
            x = jnp.concatenate([xa, xb])[rows]
            return _ln(x, norm_f, norm_f_b, m["eps"]) @ embed_w.T

        head = jax.jit(head)
        need = max([n_score] + [len(s) - f for s, f in zip(seqs,
                                                            score_from)])
        out = np.zeros((len(seqs), need, m["V"]), np.float32)
        for j, (s, f) in enumerate(zip(seqs, score_from)):
            n = len(s) - f
            for at in range(0, n, _SCORE):
                # _SCORE rows in a row lie in two neighbouring blocks
                b = (f + at) // _BLOCK
                rows = np.minimum(np.arange(_SCORE) + f + at,
                                  len(s) - 1) - b * _BLOCK
                got = np.asarray(head(
                    xs[j][b], xs[j][min(b + 1, blocks[j] - 1)],
                    jnp.asarray(rows.astype(np.int32)), top["norm_f"],
                    top["norm_f_b"], top["embed"]))
                out[j, at:min(at + _SCORE, n)] = got[:min(_SCORE, n - at)]
        return out
