"""A decoder-hybrid-decoder (the Phi-4-mini-flash / SambaY family,
``model_type`` ``phi4flash``): state-space layers beside attention, and a
second half that computes no keys, values or states of its own.

With ``L`` layers (32), ``LN`` a LayerNorm with weight and bias, every
layer is ``x += Mix_i(LN1_i(x))`` then ``x += (silu(g) * u) W_2`` with
``g, u = LN2_i(x) W_gate, LN2_i(x) W_up`` (the two halves of ``fc1``).
``Mix_i``, with ``h`` its normed input:

*i even, i <= L/2: Mamba-1* (kind ``ssm``; layer L/2, ``ssm_keep``, also
keeps its scan output ``m`` for the step).  ``[u | z] = h W_in``;
``u = silu(conv(u))``, a causal depthwise convolution of ``d_conv`` taps
with bias; ``[r | B | C] = u W_x``; ``delta = softplus(r W_dt + b_dt)``;
``A = -exp(A_log)``; ``s_t = exp(delta_t A) s_{t-1} + (delta_t u_t) (x)
B_t``; ``y_t = s_t C_t + D u_t``; ``Mix = (y * silu(z)) W_out``.  A
sequence carries ``s`` [d_state, d_inner] and the convolution's last
``d_conv - 1`` inputs from token to token.

*i even, i > L/2: a gated memory unit* (``gmu``):
``Mix = (m * silu(h W_in)) W_out`` with ``m`` layer L/2's, of the same
rows of the same step.  No state, no cache.

*i odd, i <= L/2 + 1: differential attention* (``diff_window`` under a
window of ``sliding_window`` positions that holds the query's own;
``diff``, layer L/2 + 1, over all).  ``[q | k | v] = h W_qkv + b``: ``nh``
query and ``kvh`` key and value heads of ``hd``, no positions.  Pair
``n < nh/2``, ``j = n // (nh/kvh)``: ``a1_n = softmax(q_{2n} k_{2j}^T /
sqrt(hd)) v_j`` and ``a2_n = softmax(q_{2n+1} k_{2j+1}^T / sqrt(hd)) v_j``
with ``v_j`` value heads 2j and 2j+1 side by side; ``lambda =
exp(lq1 . lk1) - exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6 exp(-0.3 i)``;
``o_n = (1 - l0) RMSNorm(a1_n - lambda a2_n; subln)``;
``Mix = [o_n] W_o + b_o``.

*i odd, i > L/2 + 1: cross attention* (``diff_cross``): ``q = h W_q + b``
alone; keys and values are layer L/2 + 1's cached rows; the same
differential form with its own lambda vectors and norm.

Final ``LN_f``; logits ``h E^T`` with the embedding ``E`` (tied).

How the engine serves it (``inference/layer_stack.py``,
``inference/serving.py``): K and V rows are cached as ``kvh / 2`` heads
of ``2 hd`` (heads 2j and 2j+1 lie side by side in the projection's own
order, so this is a reshape), a query head widened with zeros on the
half it does not see, and the ragged kernel as it is returns ``a1`` and
``a2`` at the value pair's width.  The stack is two repeats and two
singles, (``ssm``, ``diff_window``) x L/4, ``ssm_keep``, ``diff``,
(``gmu``, ``diff_cross``) x (L/4 - 1): the model holds each repeat's
weights STACKED and a step program scans over them (``periods``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from types import SimpleNamespace

from .. import nn
from .mla_moe import _Leaves


@dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    # Mamba-1's defaults (the published config has no key for them)
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0                  # 0: ceil(hidden_size / 16)

    architecture = "phi4flash"
    # The sampled rows' chain (three sorts over the vocabulary) is 16 MB
    # of compiled code at 200064 tokens, in the program of every token
    # bucket, though only a launch that holds a sampled row runs it:
    # the engine compiles it ONCE, as a program of its own behind the
    # step program (``LLMEngine._tail_apart``)
    sampled_tail_apart = True

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.mb_per_layer != 2 or L % 4 or L < 12:
            raise ValueError(
                f"{L} layers at mb_per_layer={self.mb_per_layer}: the "
                "stack is (ssm, window) x L/4, ssm, full, (gmu, cross) x "
                "(L/4 - 1), which takes a multiple of 4 layers, 12 or more")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.num_attention_heads % 2 \
                or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs query heads and "
                             "key/value heads: both counts must be even")
        if not self.dt_rank:
            self.dt_rank = -(-self.hidden_size // 16)

    # what the engine reads under the names it has
    @property
    def rms_norm_eps(self) -> float:
        return self.layer_norm_eps

    @property
    def sliding_window_size(self) -> int:
        return self.sliding_window

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size

    def page_shape(self) -> tuple:
        """(heads, width) of a cached K or V row as the pools hold it:
        key/value heads 2j and 2j+1 side by side."""
        return self.num_key_value_heads // 2, 2 * self.head_size

    def state_shapes(self, slots: int) -> tuple:
        """Shapes of the two per-sequence state arrays, ``slots`` rows
        each: the convolution's last inputs and the scan's state."""
        n = self.num_hidden_layers // 4 + 1
        return ((n, slots, self.d_conv - 1, self.d_inner),
                (n, slots, self.d_state, self.d_inner))

    def layer_kinds(self) -> list:
        """(mixer kind, FFN kind) of every layer."""
        half = self.num_hidden_layers // 2
        out = []
        for i in range(self.num_hidden_layers):
            if i % 2 == 0:
                kind = "ssm" if i < half else "ssm_keep" if i == half \
                    else "gmu"
            else:
                kind = "diff_window" if i < half else "diff" \
                    if i == half + 1 else "diff_cross"
            out.append((kind, "swiglu"))
        return out

    def periods(self) -> list:
        """[(kinds of one period, repeats, index)]: the stack as runs of
        a period of layers whose weights the model holds stacked over
        the repeats (``index`` None: a repeat's number is each layer's
        index into its pools) and single layers (``index``: its index)."""
        q = self.num_hidden_layers // 4
        sw = "swiglu"
        return [((("ssm", sw), ("diff_window", sw)), q, None),
                ((("ssm_keep", sw),), 1, q),
                ((("diff", sw),), 1, 0),
                ((("gmu", sw), ("diff_cross", sw)), q - 1, None)]

    def step_fields(self, dtype) -> dict:
        """What this model hands a step program's context beside its
        kinds (``layer_stack.step_context``): its stack as ``periods``,
        the ``norm`` of its layers (a LayerNorm with weight and bias
        ``<name>`` / ``<name>_b``) and the ``memory`` one layer leaves
        for later ones of the same step (its width, in the served
        ``dtype``).  A model that hands none of them is a stack of
        single layers under RMSNorm with nothing between layers."""
        eps = self.layer_norm_eps
        return dict(
            periods=self.periods(), memory=(self.d_inner, dtype),
            norm=lambda x, p, name: layer_norm(x, p[name], p[name + "_b"],
                                               eps))

    def attention_by_kind(self) -> dict:
        """What a step program hands each mixer kind (``c.attn[kind]``)."""
        half = self.num_hidden_layers // 2
        kvh, d = self.page_shape()
        ssm = dict(di=self.d_inner, n=self.d_state, rank=self.dt_rank,
                   taps=self.d_conv)

        def diff(depth0, **kw):
            return SimpleNamespace(nh=self.num_attention_heads, kvh=kvh, d=d,
                                   hd=self.head_size, depth0=depth0,
                                   stride=2, eps=self.layer_norm_eps, **kw)

        return {
            "ssm": SimpleNamespace(keep=False, **ssm),
            "ssm_keep": SimpleNamespace(keep=True, **ssm),
            "gmu": SimpleNamespace(di=self.d_inner),
            "diff_window": diff(1, window=self.sliding_window, reads=None),
            "diff": diff(half + 1, window=None, reads=None),
            "diff_cross": diff(half + 3, window=None, reads="diff"),
        }

    @staticmethod
    def tiny(vocab=96, hidden=32, layers=12, heads=4, kv_heads=2, ffn=64,
             window=24, seq=256, d_state=8):
        return Phi4FlashConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=ffn,
            num_hidden_layers=layers, num_attention_heads=heads,
            num_key_value_heads=kv_heads, sliding_window=window,
            max_position_embeddings=seq, d_state=d_state, dt_rank=4)


# ---------------------------------------------------------------------------
# leaves
# ---------------------------------------------------------------------------

def top_leaves(cfg: Phi4FlashConfig) -> list:
    V, H = cfg.vocab_size, cfg.hidden_size
    return [("embed", (V, H), "embedding"), ("norm_f", (H,), "norm"),
            ("norm_f_b", (H,), "zero")]


def kind_leaves(cfg: Phi4FlashConfig, kind: str) -> list:
    """[(name, shape, how it is drawn)] of ONE layer of ``kind``."""
    H, F = cfg.hidden_size, cfg.intermediate_size
    di, n, r, taps = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
    nh, kvh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_size)
    lam = [(f"l{x}", (hd,), "norm") for x in ("q1", "k1", "q2", "k2")]
    mixer = {
        "ssm": [("w_in", (H, 2 * di), "matrix"),
                ("conv_w", (taps, di), "matrix"), ("conv_b", (di,), "zero"),
                ("w_x", (di, r + 2 * n), "matrix"),
                ("w_dt", (r, di), "matrix"), ("b_dt", (di,), "norm"),
                ("A_log", (n, di), "norm"), ("D", (di,), "norm"),
                ("w_out", (di, H), "matrix")],
        "gmu": [("w_in", (H, di), "matrix"), ("w_out", (di, H), "matrix")],
        "diff": [("wqkv", (H, (nh + 2 * kvh) * hd), "matrix"),
                 ("bqkv", ((nh + 2 * kvh) * hd,), "zero")] + lam
        + [("subln", (2 * hd,), "norm"), ("wo", (nh * hd, H), "matrix"),
           ("bo", (H,), "zero")],
        "diff_cross": [("wq", (H, nh * hd), "matrix"),
                       ("bq", (nh * hd,), "zero")] + lam
        + [("subln", (2 * hd,), "norm"), ("wo", (nh * hd, H), "matrix"),
           ("bo", (H,), "zero")],
    }
    mixer["ssm_keep"] = mixer["ssm"]
    mixer["diff_window"] = mixer["diff"]
    return [("ln1", (H,), "norm"), ("ln1_b", (H,), "zero")] + mixer[kind] \
        + [("ln2", (H,), "norm"), ("ln2_b", (H,), "zero"),
           ("gate", (H, F), "matrix"), ("up", (H, F), "matrix"),
           ("down", (F, H), "matrix")]


def published(name: str, drawn):
    """A drawn leaf as its published initialisation has it, for the
    leaves whose scale decides whether a state lives: ``A_log`` =
    log(1..N) down the states (with the draw's deviation about it), ``D``
    near 1, ``softplus(b_dt)`` log-uniform over 1e-3..1e-1, the lambda
    vectors at deviation 0.1 about 0, the convolution's taps at the
    deviation of a uniform draw over +-1/sqrt(taps), the embedding (the
    head too: it is tied) at the family's 0.02, not the draw's unit
    deviation, at which a token's logit for itself drowns every other.
    Every other leaf is returned as drawn."""
    import jax.numpy as jnp
    f = drawn.astype(jnp.float32)
    if name == "A_log":
        n = drawn.shape[-2]
        states = jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
        out = states[:, None] + (f - 1.0)
    elif name == "b_dt":
        # the draw is 1 + 0.1 x: (x / 3 + 1) / 2, clipped, is a share
        share = jnp.clip(((f - 1.0) / 0.3 + 1.0) / 2.0, 0.0, 1.0)
        dt = jnp.exp(share * (math.log(1e-1) - math.log(1e-3))
                     + math.log(1e-3))
        out = dt + jnp.log(-jnp.expm1(-dt))          # softplus's inverse
    elif name in ("lq1", "lk1", "lq2", "lk2"):
        out = f - 1.0
    elif name == "embed":
        out = f * 0.02
    elif name == "conv_w":
        taps, di = drawn.shape[-2:]
        out = f * (math.sqrt((taps + di) / 2.0) / math.sqrt(3.0 * taps))
    else:
        return drawn
    return out.astype(drawn.dtype)


# ---------------------------------------------------------------------------
# the layers' arithmetic (what ``inference/layer_stack.py`` and
# ``forward`` below share)
# ---------------------------------------------------------------------------

def layer_norm(x, w, b, eps):
    """LayerNorm in float32 with weight and bias, cast back."""
    import jax.numpy as jnp
    from jax import lax
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    xc = xf - mu
    o = xc * lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps)
    return (o * w.astype(jnp.float32) + b.astype(jnp.float32)) \
        .astype(x.dtype)


def conv_taps(ext, p):
    """The causal depthwise convolution of rows whose ``taps - 1``
    predecessors stand before them: ``ext`` [taps - 1 + T, di] ->
    [T, di] float32, ``out_t = sum_j w[j] ext[t + j] + b``."""
    import jax.numpy as jnp
    w = p["conv_w"].astype(jnp.float32)
    taps = w.shape[0]
    T = ext.shape[0] - (taps - 1)
    ext = ext.astype(jnp.float32)
    return sum(w[j] * ext[j:j + T] for j in range(taps)) \
        + p["conv_b"].astype(jnp.float32)


def ssm_maps(u, p, a, mm):
    """(delta [T, di] float32, A [n, di] float32, B, C [T, n]) from the
    convolved input ``u``."""
    import jax
    import jax.numpy as jnp
    rbc = mm(u, p, "w_x")
    r, Bm, Cm = (rbc[:, :a.rank], rbc[:, a.rank:a.rank + a.n],
                 rbc[:, a.rank + a.n:])
    delta = jax.nn.softplus(mm(r, p, "w_dt").astype(jnp.float32)
                            + p["b_dt"].astype(jnp.float32))
    return delta, -jnp.exp(p["A_log"].astype(jnp.float32)), Bm, Cm


def widen(q):
    """q [T, nh, hd] -> [T, nh, 2 hd]: an even head keeps the first half
    and zeros the second, an odd head the other way round, so that over
    key heads 2j and 2j+1 side by side an even head scores against 2j
    alone and an odd head against 2j+1 alone."""
    import jax.numpy as jnp
    T, nh, hd = q.shape
    q = q.reshape(T, nh // 2, 2, hd)
    z = jnp.zeros_like(q[:, :, 0])
    return jnp.stack([jnp.concatenate([q[:, :, 0], z], -1),
                      jnp.concatenate([z, q[:, :, 1]], -1)],
                     axis=2).reshape(T, nh, 2 * hd)


def lambda_init(depth):
    """``l0`` of the layer at ``depth`` (an int, or a traced scalar)."""
    import jax.numpy as jnp
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


def diff_combine(att, p, l0, eps):
    """att [T, nh, 2 hd] (a1 of pair n at head 2n, a2 at 2n + 1) ->
    [T, nh * hd]: ``(1 - l0) RMSNorm(a1 - lambda a2; subln)``."""
    import jax.numpy as jnp
    from jax import lax
    f32 = jnp.float32
    T, nh, w = att.shape
    a = att.astype(f32).reshape(T, nh // 2, 2, w)
    lam = jnp.exp(jnp.sum(p["lq1"].astype(f32) * p["lk1"].astype(f32))) \
        - jnp.exp(jnp.sum(p["lq2"].astype(f32) * p["lk2"].astype(f32))) + l0
    o = a[:, :, 0] - lam * a[:, :, 1]
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
        * p["subln"].astype(f32)
    return ((1.0 - l0) * o).astype(att.dtype).reshape(T, nh // 2 * w)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _stacked(cfg, kind: str, n: int) -> list:
    """``kind_leaves`` with a leading axis over ``n`` repeats (none for
    a single layer)."""
    lead = (n,) if n > 1 else ()
    return [(name, lead + shape, how)
            for name, shape, how in kind_leaves(cfg, kind)]


class Phi4FlashForCausalLM(nn.Layer):
    """The decoder as ``LLMEngine`` takes it (``.config``,
    ``.parameters()``, ``decode_params()``).  ``groups[g][k]`` holds the
    leaves of kind ``k`` of period ``g``, each with a leading axis over
    the period's repeats where it repeats; with ``materialize=False``
    nothing is drawn or allocated."""

    def __init__(self, config: Phi4FlashConfig, dtype="bfloat16",
                 materialize: bool = True, seed: int = 0):
        import jax
        import jax.numpy as jnp
        super().__init__()
        self.config = config
        dt = jnp.dtype(dtype)
        key = jax.random.PRNGKey(seed)
        self.top = _Leaves(top_leaves(config), dt, materialize,
                           jax.random.fold_in(key, 0))
        if materialize:
            emb = self.top._parameters["embed"]
            emb._data = published("embed", emb._data)
        groups = []
        for g, (kinds, n, _index) in enumerate(config.periods()):
            row = []
            for k, (kind, _ffn) in enumerate(kinds):
                leaves = _Leaves(_stacked(config, kind, n), dt, materialize,
                                 jax.random.fold_in(key, 1 + 2 * g + k))
                if materialize:
                    for name, par in leaves._parameters.items():
                        par._data = published(name, par._data)
                row.append(leaves)
            groups.append(nn.LayerList(row))
        self.groups = nn.LayerList(groups)

    def decode_params(self) -> dict:
        """The model's own arrays: nothing is stacked or copied here (a
        repeat's leaves are HELD stacked).  ``layers[g]`` is period g's
        tuple of {name: array}, one a kind."""
        return {**self.top.arrays(),
                "layers": [tuple(leaves.arrays() for leaves in row)
                           for row in self.groups]}

    def layer_params(self) -> list:
        """{name: array} of every layer in depth order (a repeat's leaf
        indexed out of its stack): for whole-sequence passes."""
        out = []
        for (kinds, n, _index), row in zip(self.config.periods(),
                                           self.groups):
            for rep in range(n):
                for leaves in row:
                    out.append({name: a[rep] if n > 1 else a
                                for name, a in leaves.arrays().items()})
        return out

    def forward(self, input_ids):
        """Logits [B, T, V] of whole sequences, float32, no cache and no
        state carried: the serving path's second opinion in the tests."""
        import jax
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        cfg = self.config
        f32 = jnp.float32
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        top = {k: a.astype(f32) for k, a in self.top.arrays().items()}
        layers = [{k: a.astype(f32) for k, a in p.items()}
                  for p in self.layer_params()]
        by_kind = cfg.attention_by_kind()
        eps = cfg.layer_norm_eps
        nh, hd = cfg.num_attention_heads, cfg.head_size
        kvh, d = cfg.page_shape()

        def mm(h, p, name):
            return h @ p[name]

        def scan(u, delta, A, Bm, Cm, D):
            def token(s, inp):
                u_t, d_t, b_t, c_t = inp
                s = jnp.exp(d_t[None] * A) * s \
                    + (d_t * u_t)[None] * b_t[:, None]
                return s, jnp.sum(s * c_t[:, None], 0) + D * u_t
            return jax.lax.scan(token, jnp.zeros_like(A),
                                (u, delta, Bm, Cm))[1]

        def attend(q, k, v, a):
            T = q.shape[0]
            pos = jnp.arange(T)
            see = pos[None, :] <= pos[:, None]
            if a.window is not None:
                see &= pos[None, :] > pos[:, None] - a.window
            qg = widen(q).reshape(T, kvh, nh // kvh, d)
            s = jnp.einsum("qhgd,khd->hgqk", qg, k) / math.sqrt(hd)
            s = jnp.where(see[None, None], s, -jnp.inf)
            return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1),
                              v).reshape(T, nh, d)

        def one(toks):
            T = toks.shape[0]
            x = top["embed"][toks]
            memory = kv = None
            for i, ((kind, _), p) in enumerate(zip(cfg.layer_kinds(),
                                                   layers)):
                a = by_kind[kind]
                h = layer_norm(x, p["ln1"], p["ln1_b"], eps)
                if kind in ("ssm", "ssm_keep"):
                    uz = h @ p["w_in"]
                    u, z = uz[:, :a.di], uz[:, a.di:]
                    ext = jnp.concatenate(
                        [jnp.zeros((a.taps - 1, a.di), f32), u])
                    u = jax.nn.silu(conv_taps(ext, p))
                    y = scan(u, *ssm_maps(u, p, a, mm), p["D"])
                    if a.keep:
                        memory = y
                    mix = (y * jax.nn.silu(z)) @ p["w_out"]
                elif kind == "gmu":
                    mix = (memory * jax.nn.silu(h @ p["w_in"])) @ p["w_out"]
                else:
                    if a.reads is None:
                        qkv = h @ p["wqkv"] + p["bqkv"]
                        q = qkv[:, :nh * hd].reshape(T, nh, hd)
                        k, v = (qkv[:, nh * hd:].reshape(T, 2, kvh, d)
                                .transpose(1, 0, 2, 3))
                        if kind == "diff":
                            kv = (k, v)
                    else:
                        q = (h @ p["wq"] + p["bq"]).reshape(T, nh, hd)
                        k, v = kv
                    att = attend(q, k, v, a)
                    mix = diff_combine(att, p, lambda_init(i), eps) \
                        @ p["wo"] + p["bo"]
                x = x + mix
                h2 = layer_norm(x, p["ln2"], p["ln2_b"], eps)
                x = x + (jax.nn.silu(h2 @ p["gate"]) * (h2 @ p["up"])) \
                    @ p["down"]
            return layer_norm(x, top["norm_f"], top["norm_f_b"], eps) \
                @ top["embed"].T

        with jax.default_matmul_precision("highest"):
            return Tensor(jnp.stack([one(t) for t in ids.astype(jnp.int32)]))
