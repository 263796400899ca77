"""A latent-attention (MLA) decoder with sparse expert layers, as one
chip of an expert-parallel deployment holds it (the ``sarvam_mla`` /
DeepSeek-V2 family: ``kv_lora_rank``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, no ``q_lora_rank``;
``first_k_dense_replace`` leading dense layers, then layers of routed
experts with shared experts and a sigmoid router with an expert bias).

Per layer, ``h = RMSNorm(x)``:

*Attention.*  ``q = W_q h`` as heads of ``[q_nope | q_rope]``, a learned
RMSNorm over each head's whole query (``use_qk_norm``);
``[c_kv | k_rope] = W_kva h``, ``c = RMSNorm_w(c_kv)``; rotary
(interleaved pairs, YaRN frequencies) on ``q_rope`` and on ``k_rope``,
which all heads share.  The cache holds ``[c | k_rope]`` a token.
Expanded: ``[k_nope | v]_h = W_kvb,h c``, ``score_h = (q_nope_h . k_nope_h
+ q_rope_h . k_rope) s``.  Absorbed (what serving runs): ``q'_h =
W_kvb,h^K^T q_nope_h``, ``score_h = (q'_h . c + q_rope_h . k_rope) s``,
``out_h = W_kvb,h^V sum p c``.  ``s = q_head_dim^-0.5 m^2``, ``m = 0.1
mscale_all_dim ln(factor) + 1``.

*Experts.*  ``s = sigmoid(W_r h2)`` over ALL experts of the model; the
``num_experts_per_tok`` largest of ``s + b``; ``g = scaling s_idx / sum
s_idx``; ``x += shared(h2) + sum over the chosen experts HELD HERE of g_i
E_i(h2)``.  The model is told which experts it holds (``experts_held``
of ``num_experts``, the ``ep_rank``-th block of ``ep_size``): what the
absent experts would add is the other chips', whose exchange this chip
runs without.  No token is dropped and none is padded to a capacity:
pairs are sorted by expert and go through a grouped product
(``ops/pallas/grouped_matmul.py``).

The functions below are the layer's arithmetic on flat tokens, under the
``jax.named_scope`` names a device trace is read by; the serving engine
(``inference/serving.py``) puts its paged cache between ``mla_project``
and ``mla_output``.  ``MlaMoeForCausalLM.forward`` is the whole-sequence
pass in the expanded form, with no cache.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

from .. import nn
from ..core.tensor import Parameter
from .llama import _rms_weight


@dataclass
class MlaMoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 16384         # the leading dense layers' FFN
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 32
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 128                 # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=lambda: {
        "type": "deepseek_yarn", "factor": 40.0,
        "original_max_position_embeddings": 4096, "beta_fast": 32.0,
        "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0})
    # this chip's share: experts_held consecutive experts from
    # ep_rank * experts_held (None: all of them)
    experts_held: int | None = None
    ep_size: int = 1
    ep_rank: int = 0

    architecture = "mla_moe"

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = self.num_experts // self.ep_size
        if self.experts_held * self.ep_size != self.num_experts:
            raise ValueError(
                f"experts_held={self.experts_held} x ep_size={self.ep_size}"
                f" is not num_experts={self.num_experts}")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank={self.ep_rank} outside "
                             f"ep_size={self.ep_size}")

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kv_row(self) -> int:
        """Numbers cached a token a layer: ``[c | k_rope]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def first_expert(self) -> int:
        return self.ep_rank * self.experts_held

    def layer_kinds(self) -> list:
        """(attention kind, FFN kind) of every layer."""
        return [("mla", "swiglu" if i < self.first_k_dense_replace
                 else "moe") for i in range(self.num_hidden_layers)]

    def attention_by_kind(self) -> dict:
        """{attention kind: the sizes of its latent attention}, what a
        step program hands the latent body (``inference/layer_stack.py``
        ``_latent``): a full query projection with a norm over each
        head's query, no window, no gate, no indexer."""
        from types import SimpleNamespace
        return {"mla": SimpleNamespace(
            nh=self.num_attention_heads, rq=0, dc=self.kv_lora_rank,
            dn=self.qk_nope_head_dim, dr=self.qk_rope_head_dim,
            dv=self.v_head_dim, q_norm=True, eps=self.rms_norm_eps,
            inv_freq=yarn_inv_freq(self), sm_scale=softmax_scale(self),
            window=None, gated=False, index=None)}

    @staticmethod
    def tiny(vocab=96, hidden=64, layers=3, heads=4, experts=8, held=None,
             ep_size=1, ep_rank=0, seq=256):
        return MlaMoeConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=layers,
            num_attention_heads=heads, kv_lora_rank=128,
            qk_nope_head_dim=16, qk_rope_head_dim=32, v_head_dim=16,
            num_experts=experts, num_experts_per_tok=3,
            max_position_embeddings=seq, experts_held=held,
            ep_size=ep_size, ep_rank=ep_rank,
            rope_scaling={"type": "deepseek_yarn", "factor": 4.0,
                          "original_max_position_embeddings": 64,
                          "beta_fast": 32.0, "beta_slow": 1.0,
                          "mscale": 1.0, "mscale_all_dim": 1.0})


# ---------------------------------------------------------------------------
# the layer's arithmetic
# ---------------------------------------------------------------------------

def yarn_frequencies(d: int, base: float, factor: float, orig: float,
                     beta_fast: float, beta_slow: float):
    """YaRN's rotary frequencies over ``d`` rotated numbers (d/2 pairs):
    each pair's frequency is the plain one (``base^(-2j/d)``) where it
    turns more than ``beta_fast`` times over the original context
    ``orig``, the plain one over ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear blend between."""
    import numpy as np
    extra = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def corr(turns):
        return d * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (extra / factor * (1.0 - keep) + extra * keep).astype(np.float32)


def yarn_inv_freq(cfg: MlaMoeConfig):
    """Rotary frequencies of ``deepseek_yarn`` over the head's
    ``qk_rope_head_dim`` rope numbers (``yarn_frequencies``)."""
    rs = cfg.rope_scaling
    return yarn_frequencies(
        cfg.qk_rope_head_dim, float(cfg.rope_theta), float(rs["factor"]),
        float(rs["original_max_position_embeddings"]),
        float(rs["beta_fast"]), float(rs["beta_slow"]))


def softmax_scale(cfg: MlaMoeConfig) -> float:
    rs = cfg.rope_scaling
    m = 0.1 * float(rs["mscale_all_dim"]) * math.log(float(rs["factor"])) + 1.0
    return cfg.q_head_dim ** -0.5 * m * m


def rope_at(x, pos, inv_freq):
    """Interleaved-pair rotation of x [T, heads, d] at positions pos [T]."""
    import jax.numpy as jnp
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(x.shape).astype(x.dtype)


def mla_project(h, p, a, pos):
    """What attention needs of h [T, H] at positions pos, by the sizes
    ``a`` of the layer's kind (``attention_by_kind``): the absorbed
    queries [T, heads, dc + dr], the rows to cache [T, dc + dr] and,
    where the query is low-rank (``a.rq``), its normed latent [T, rq]
    (else None), which an indexer's queries are made from too."""
    import jax
    import jax.numpy as jnp
    T = h.shape[0]
    nh, dn, dr, dc, eps = a.nh, a.dn, a.dr, a.dc, a.eps
    with jax.named_scope("q_proj"):
        c_q = None
        if a.rq:
            c_q = _rms_weight(h @ p["wqa"], p["q_a_norm"], eps)
            q = (c_q @ p["wqb"]).reshape(T, nh, dn + dr)
        else:
            q = (h @ p["wq"]).reshape(T, nh, dn + dr)
        if a.q_norm:
            q = _rms_weight(q, p["q_norm"], eps)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        w_k = p["wkvb"].reshape(dc, nh, dn + a.dv)[..., :dn]
        q_abs = jnp.einsum("thd,chd->thc", q_nope, w_k)
    with jax.named_scope("kv_latent"):
        ckv = h @ p["wkva"]
        c = _rms_weight(ckv[:, :dc], p["kv_norm"], eps)
        k_rope = ckv[:, None, dc:]
    with jax.named_scope("rope"):
        q_rope = rope_at(q_rope, pos, a.inv_freq)
        k_rope = rope_at(k_rope, pos, a.inv_freq)[:, 0]
    return (jnp.concatenate([q_abs, q_rope], -1),
            jnp.concatenate([c, k_rope], -1), c_q)


def mla_output(lat, p, a, gate=None):
    """The attention block's contribution to x from lat [T, heads, dc],
    each head's weighted sum of cached latents; ``gate`` [T, heads]
    float32 (a headwise output gate): each head's output times its
    number before ``W_o``."""
    import jax
    import jax.numpy as jnp
    T = lat.shape[0]
    nh, dn, dv, dc = a.nh, a.dn, a.dv, a.dc
    with jax.named_scope("o_proj"):
        w_v = p["wkvb"].reshape(dc, nh, dn + dv)[..., dn:]
        v = jnp.einsum("thc,chd->thd", lat, w_v)
    if gate is not None:
        with jax.named_scope("attn_gate"):
            v = (v.astype(jnp.float32) * gate[..., None]).astype(v.dtype)
    with jax.named_scope("o_proj"):
        return v.reshape(T, nh * dv) @ p["wo"]


def swiglu(h, gate, up, down):
    import jax
    import jax.numpy as jnp
    a = jax.nn.silu((h @ gate).astype(jnp.float32)).astype(h.dtype) \
        * (h @ up)
    return a @ down


def route(h2, p, cfg: MlaMoeConfig):
    """(idx [T, k] expert ids over ALL experts, g [T, k] float32 gates).
    The scores are taken in float32: two experts' scores a bfloat16 apart
    would otherwise change places, and a changed expert is a changed
    token."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(h2, p["router"], preferred_element_type=jnp.float32)
    s = jax.nn.sigmoid(logits)
    # a model whose router has no expert bias has no such leaf
    pick = s + p["router_bias"].astype(jnp.float32) \
        if "router_bias" in p else s
    _, idx = jax.lax.top_k(pick, cfg.num_experts_per_tok)
    w = jnp.take_along_axis(s, idx, axis=-1)
    g = cfg.routed_scaling_factor * w / jnp.sum(w, -1, keepdims=True)
    return idx, g


def routed_experts(h2, idx, g, p, *, first: int, held: int, valid,
                   use_kernel: bool, gate: str = "silu"):
    """The chosen experts held here, for tokens h2 [T, H] that chose
    experts idx [T, k] (ids over ALL experts) with gates g [T, k]: pairs
    sorted by expert, the two grouped products (``gate`` names the
    function on the gate's half: ``silu``, ``relu``), and the weighted
    sum back in token order.  Returns (out [T, H] in h2's type, pairs
    computed here, pairs at each held expert [held])."""
    import jax
    import jax.numpy as jnp

    from ..ops.pallas import grouped_matmul as _gm
    T, H = h2.shape
    k = idx.shape[-1]
    with jax.named_scope("moe_dispatch"):
        local = idx - first
        here = (local >= 0) & (local < held) & valid[:, None]
        # pairs sorted by expert; what is not computed here sorts last
        e = jnp.where(here, local, held).reshape(T * k)
        order = jnp.argsort(e, stable=True)
        sizes = jnp.bincount(e, length=held + 1).astype(jnp.int32)[:held]
        xs = h2[order // k]                                # [T*k, H]
    with jax.named_scope("moe_experts"):
        a = _gm.grouped_swiglu(xs, p["e_gate"], p["e_up"], sizes,
                               use_kernel=use_kernel, gate=gate)
        y = _gm.grouped_matmul(a, p["e_down"], sizes,
                               use_kernel=use_kernel)
    with jax.named_scope("moe_combine"):
        n_here = jnp.sum(sizes)
        # rows past the last group were not computed: whatever they hold
        y = jnp.where((jnp.arange(T * k) < n_here)[:, None], y, 0.0)
        back = jnp.argsort(order)                          # pair -> its row
        y = y[back].reshape(T, k, H) * (g * here)[..., None]
        out = jnp.sum(y, axis=1).astype(h2.dtype)
    return out, n_here, sizes


def expert_counts(n_here, sizes, valid, k: int):
    """What an expert layer counted, int32 [4]: pairs computed here,
    pairs routed anywhere, experts here that got a token, most tokens at
    one expert here."""
    import jax.numpy as jnp
    return jnp.stack([n_here, jnp.sum(valid).astype(jnp.int32) * k,
                      jnp.sum(sizes > 0).astype(jnp.int32),
                      jnp.max(sizes)]).astype(jnp.int32)


def moe_ffn(h2, p, cfg: MlaMoeConfig, valid=None, use_kernel=False):
    """The expert layer's contribution to x from h2 [T, H], and its
    counts (``expert_counts``).  ``valid`` [T] bool marks real tokens; a
    launch's padding is routed nowhere.  ``use_kernel``: the grouped
    products by the Pallas kernel (the serving engine on a TPU), else by
    ``lax.ragged_dot``."""
    import jax
    import jax.numpy as jnp
    if valid is None:
        valid = jnp.ones((h2.shape[0],), bool)
    with jax.named_scope("router"):
        idx, g = route(h2, p, cfg)
    out, n_here, sizes = routed_experts(
        h2, idx, g, p, first=cfg.first_expert, held=cfg.experts_held,
        valid=valid, use_kernel=use_kernel)
    with jax.named_scope("shared_expert"):
        out = out + swiglu(h2, p["s_gate"], p["s_up"], p["s_down"])
    return out, expert_counts(n_here, sizes, valid,
                              cfg.num_experts_per_tok)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def layer_leaves(cfg: MlaMoeConfig, i: int) -> list:
    """[(name, shape, kind)] of layer i's weights; kinds as a seeded draw
    takes them: ``norm`` near 1, ``matrix`` Xavier over the last two
    dimensions, ``zero``."""
    H, nh = cfg.hidden_size, cfg.num_attention_heads
    dq, dc, dr = cfg.q_head_dim, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    out = [("ln1", (H,), "norm"), ("wq", (H, nh * dq), "matrix"),
           ("q_norm", (dq,), "norm"), ("wkva", (H, dc + dr), "matrix"),
           ("kv_norm", (dc,), "norm"),
           ("wkvb", (dc, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
            "matrix"),
           ("wo", (nh * cfg.v_head_dim, H), "matrix"), ("ln2", (H,), "norm")]
    if i < cfg.first_k_dense_replace:
        F = cfg.intermediate_size
        return out + [("gate", (H, F), "matrix"), ("up", (H, F), "matrix"),
                      ("down", (F, H), "matrix")]
    Fe, E = cfg.moe_intermediate_size, cfg.experts_held
    Fs = Fe * cfg.num_shared_experts
    return out + [("router", (H, cfg.num_experts), "matrix"),
                  ("router_bias", (cfg.num_experts,), "zero"),
                  ("e_gate", (E, H, Fe), "matrix"),
                  ("e_up", (E, H, Fe), "matrix"),
                  ("e_down", (E, Fe, H), "matrix"),
                  ("s_gate", (H, Fs), "matrix"), ("s_up", (H, Fs), "matrix"),
                  ("s_down", (Fs, H), "matrix")]


def top_leaves(cfg: MlaMoeConfig) -> list:
    V, H = cfg.vocab_size, cfg.hidden_size
    return [("embed", (V, H), "embedding"), ("norm_f", (H,), "norm"),
            ("head", (H, V), "matrix")]


def _draw(key, shape, kind, dtype):
    import jax
    import jax.numpy as jnp
    if kind == "zero":
        return jnp.zeros(shape, dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "norm":
        x = 1.0 + 0.1 * x
    elif kind == "matrix":
        x = x * math.sqrt(2.0 / (shape[-2] + shape[-1]))
    return x.astype(dtype)


@functools.lru_cache(maxsize=None)
def _draw_program():
    import jax
    return jax.jit(_draw, static_argnums=(1, 2, 3))


class _Leaves(nn.Layer):
    """A layer whose parameters are the named leaves it is given.  With
    ``materialize=False`` each parameter holds its ShapeDtypeStruct and
    no device array: the caller is about to hand it one."""

    def __init__(self, leaves, dtype, materialize, key):
        import jax
        import jax.numpy as jnp
        super().__init__()
        for n, (name, shape, kind) in enumerate(leaves):
            if materialize:
                data = _draw_program()(jax.random.fold_in(key, n), shape,
                                       kind, dtype)
                p = Parameter(data)
            else:
                p = Parameter(jnp.zeros((), dtype))
                p._data = jax.ShapeDtypeStruct(shape, dtype)
            self.add_parameter(name, p)

    def arrays(self) -> dict:
        return {name: p._data for name, p in self._parameters.items()}


class MlaMoeForCausalLM(nn.Layer):
    """The decoder as ``LLMEngine`` takes it (``.config``,
    ``.parameters()``, ``decode_params()``).  Weights are drawn leaf by
    leaf in ``dtype`` itself (no float32 model first); with
    ``materialize=False`` nothing is drawn or allocated."""

    def __init__(self, config: MlaMoeConfig, dtype="bfloat16",
                 materialize: bool = True, seed: int = 0):
        import jax
        import jax.numpy as jnp
        super().__init__()
        self.config = config
        dt = jnp.dtype(dtype)
        key = jax.random.PRNGKey(seed)
        self.top = _Leaves(top_leaves(config), dt, materialize,
                           jax.random.fold_in(key, 0))
        self.layers = nn.LayerList([
            _Leaves(layer_leaves(config, i), dt, materialize,
                    jax.random.fold_in(key, i + 1))
            for i in range(config.num_hidden_layers)])

    def decode_params(self) -> dict:
        """The model's own arrays, layer by layer (nothing is stacked or
        copied: the engine's weights are these)."""
        return {**self.top.arrays(),
                "layers": [lyr.arrays() for lyr in self.layers]}

    def forward(self, input_ids):
        """Logits [B, T, V] of whole sequences, expanded form, float32,
        no cache: the serving path's second opinion in the tests."""
        import jax
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        cfg = self.config
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        f32 = lambda t: jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), t)
        params = f32(self.decode_params())
        inv, scale = yarn_inv_freq(cfg), softmax_scale(cfg)
        nh, dn, dr, dv, dc = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                              cfg.qk_rope_head_dim, cfg.v_head_dim,
                              cfg.kv_lora_rank)
        eps = cfg.rms_norm_eps

        def one(toks):
            T = toks.shape[0]
            pos = jnp.arange(T)
            x = params["embed"][toks]
            for p, (_a, ffn) in zip(params["layers"], cfg.layer_kinds()):
                h = _rms_weight(x, p["ln1"], eps)
                q = _rms_weight((h @ p["wq"]).reshape(T, nh, dn + dr),
                         p["q_norm"], eps)
                ckv = h @ p["wkva"]
                c = _rms_weight(ckv[:, :dc], p["kv_norm"], eps)
                kv = (c @ p["wkvb"]).reshape(T, nh, dn + dv)
                q_rope = rope_at(q[..., dn:], pos, inv)
                k_rope = rope_at(ckv[:, None, dc:], pos, inv)[:, 0]
                s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], kv[..., :dn])
                     + jnp.einsum("qhr,kr->hqk", q_rope, k_rope)) * scale
                s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s,
                              -jnp.inf)
                att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                 kv[..., dn:])
                x = x + att.reshape(T, nh * dv) @ p["wo"]
                h2 = _rms_weight(x, p["ln2"], eps)
                if ffn == "swiglu":
                    x = x + swiglu(h2, p["gate"], p["up"], p["down"])
                else:
                    x = x + moe_ffn(h2, p, cfg)[0]
            return _rms_weight(x, params["norm_f"], eps) @ params["head"]

        with jax.default_matmul_precision("highest"):
            return Tensor(jnp.stack([one(t) for t in ids.astype(jnp.int32)]))
