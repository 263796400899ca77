"""A decoder whose FULL-attention and SLIDING-window layers differ in
more than the window: each kind has its own number of query heads over
the same K/V heads, its own rotary embedding (the full layers rotate
part of each head with YaRN frequencies, the sliding layers the whole
head with plain ones) and a gate on the attention output; a leading
dense layer, then sparse layers of many small experts, all held here,
beside a shared one (the Laguna family: ``layer_types``,
``num_attention_heads_per_layer``, ``rope_parameters``, ``gating``,
``mlp_layer_types``, ``shared_expert_intermediate_size``,
``moe_routed_scaling_factor``).

Layer ``i``, ``h = RMSNorm(x; ln1)``, ``n_i =
num_attention_heads_per_layer[i]``:

*Attention.*  ``q = h W_q`` as ``n_i`` heads of ``head_dim``, ``k, v``
as ``num_key_value_heads`` heads; no bias, no QK-norm.  Rotary on q and
k by the layer's kind (``rope_parameters[layer_types[i]]``): the first
``head_dim * partial_rotary_factor`` numbers of each head are rotated
(interleaved pairs), the rest pass through; ``rope_type`` ``yarn``
blends the frequencies over those rotated numbers
(``mla_moe.yarn_frequencies``) and multiplies cos and sin by
``attention_factor``, so the rotated part of q and of k grows by it.
Scores ``q k^T / sqrt(head_dim)``, causal; a sliding layer's query at
position i sees keys ``i - sliding_window < j <= i``.  With ``gating``:
``att <- att * sigmoid(h W_g)`` elementwise, ``W_g`` as wide as ``W_q``;
then ``x += att W_o``.

*FFN.*  ``h2 = RMSNorm(x; ln2)``.  ``mlp_layer_types[i]`` ``dense``:
SwiGLU of ``intermediate_size``.  ``sparse``: ``mla_moe``'s router and
expert layer (sigmoid scores over all experts in float32, the
``num_experts_per_tok`` largest, gates normalised over the taken and
scaled by ``moe_routed_scaling_factor``, weighing the experts' OUTPUT)
with no expert bias, every expert held, and one shared expert of
``shared_expert_intermediate_size``.

The serving engine keeps the two kinds of layer in two page pools with a
block table each (``inference/serving.py``) and hands each kind its own
head count and rotary (``attention_by_kind``).
``LagunaForCausalLM.forward`` is the whole-sequence pass with no cache,
the window as a mask on the full score matrix.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from types import SimpleNamespace

from .. import nn
from .llama import _rms_weight
from .mla_moe import (_Leaves, moe_ffn, swiglu, top_leaves,
                      yarn_frequencies)

FULL, SLIDING = "full_attention", "sliding_attention"


def _period4(n: int, first, rest) -> list:
    return [first if i % 4 == 0 else rest for i in range(n)]


def _rope_parameters() -> dict:
    return {FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                   "original_max_position_embeddings": 4096,
                   "beta_slow": 1, "beta_fast": 64,
                   "attention_factor": 1.4158883083359672,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 10000,
                      "partial_rotary_factor": 1}}


@dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192          # the dense layers' FFN
    num_hidden_layers: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    gating: bool = True
    sliding_window: int = 512
    # by layer; longer than the depth is fine: a cut model reads its
    # first num_hidden_layers entries
    layer_types: list = field(
        default_factory=lambda: _period4(40, FULL, SLIDING))
    num_attention_heads_per_layer: list = field(
        default_factory=lambda: _period4(40, 48, 64))
    mlp_layer_types: list = field(
        default_factory=lambda: ["dense"] + ["sparse"] * 39)
    rope_parameters: dict = field(default_factory=_rope_parameters)
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6

    architecture = "laguna"

    def __post_init__(self):
        n = self.num_hidden_layers
        for name in ("layer_types", "num_attention_heads_per_layer",
                     "mlp_layer_types"):
            if len(getattr(self, name)) < n:
                raise ValueError(f"{name} has {len(getattr(self, name))} "
                                 f"entries for {n} layers")
        for kind in (FULL, SLIDING):
            heads = {self.num_attention_heads_per_layer[i]
                     for i in range(n) if self.layer_types[i] == kind}
            if len(heads) > 1:
                raise ValueError(
                    f"{kind} layers with {sorted(heads)} query heads: the "
                    "step programs keep one head count an attention kind")
            if any(h % self.num_key_value_heads for h in heads):
                raise ValueError(
                    f"{sorted(heads)} query heads over "
                    f"{self.num_key_value_heads} K/V heads")

    # what ``mla_moe.moe_ffn`` reads of a configuration: every expert is
    # held here
    @property
    def routed_scaling_factor(self) -> float:
        return self.moe_routed_scaling_factor

    @property
    def experts_held(self) -> int:
        return self.num_experts

    first_expert = 0

    @property
    def sliding_window_size(self) -> int:
        return self.sliding_window

    @property
    def num_attention_heads(self) -> int:
        """The first layer's (the published key's value); a layer's own
        is ``num_attention_heads_per_layer[i]``."""
        return self.num_attention_heads_per_layer[0]

    def is_window(self, i: int) -> bool:
        return self.layer_types[i] == SLIDING

    def is_sparse(self, i: int) -> bool:
        return self.mlp_layer_types[i] == "sparse"

    def attention_kind(self, i: int) -> str:
        return ("gqa_gated" if self.gating else "gqa") \
            + ("_window" if self.is_window(i) else "")

    def layer_kinds(self) -> list:
        """(attention kind, FFN kind) of every layer."""
        return [(self.attention_kind(i),
                 "moe" if self.is_sparse(i) else "swiglu")
                for i in range(self.num_hidden_layers)]

    def rotary(self, layer_type: str):
        """(frequencies float32 [rotated numbers of a head / 2], the
        factor on cos and sin) of a kind of layer."""
        import numpy as np
        rp = self.rope_parameters[layer_type]
        rot = int(self.head_dim * float(rp.get("partial_rotary_factor", 1)))
        base = float(rp["rope_theta"])
        if rp.get("rope_type", "default") == "yarn":
            inv = yarn_frequencies(
                rot, base, float(rp["factor"]),
                float(rp["original_max_position_embeddings"]),
                float(rp["beta_fast"]), float(rp["beta_slow"]))
            return inv, float(rp["attention_factor"])
        inv = base ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
        return inv.astype(np.float32), 1.0

    def attention_by_kind(self) -> dict:
        """{attention kind: its query heads ``nh`` and its rotary
        ``rope(x [T, heads, d], pos [T])``} for the kinds this model's
        layers have: what a step program hands each kind
        (``inference/layer_stack.py``)."""
        out = {}
        for i in range(self.num_hidden_layers):
            kind = self.attention_kind(i)
            if kind in out:
                continue
            inv, scale = self.rotary(self.layer_types[i])
            rope = functools.partial(rope_partial, inv_freq=inv, scale=scale)
            out[kind] = SimpleNamespace(
                nh=self.num_attention_heads_per_layer[i], rope=rope)
        return out

    @staticmethod
    def tiny(vocab=96, hidden=48, layers=7, full_heads=6, window_heads=8,
             kv_heads=2, head_dim=16, experts=16, active=4, ffn=32,
             dense_ffn=64, window=32, seq=256, gating=True,
             full_rotary=0.5):
        rp = _rope_parameters()
        rp[FULL].update(factor=4, original_max_position_embeddings=64,
                        beta_fast=8, partial_rotary_factor=full_rotary)
        return LagunaConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=dense_ffn,
            num_hidden_layers=layers, num_key_value_heads=kv_heads,
            head_dim=head_dim, num_experts=experts,
            num_experts_per_tok=active, moe_intermediate_size=ffn,
            shared_expert_intermediate_size=ffn, gating=gating,
            sliding_window=window,
            layer_types=_period4(layers, FULL, SLIDING),
            num_attention_heads_per_layer=_period4(layers, full_heads,
                                                   window_heads),
            mlp_layer_types=["dense"] + ["sparse"] * (layers - 1),
            rope_parameters=rp, max_position_embeddings=seq)


# ---------------------------------------------------------------------------
# the layer's arithmetic
# ---------------------------------------------------------------------------

def rope_partial(x, pos, inv_freq, scale: float = 1.0):
    """x [T, heads, d] at positions pos [T]: the first ``2 *
    len(inv_freq)`` numbers of each head rotated (interleaved pairs) with
    cos and sin times ``scale``, the rest passed through."""
    import jax.numpy as jnp
    rot = 2 * len(inv_freq)
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(inv_freq)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x[..., :rot].astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    out = out.reshape(xf.shape).astype(x.dtype)
    if rot == x.shape[-1]:
        return out
    return jnp.concatenate([out, x[..., rot:]], -1)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def layer_leaves(cfg: LagunaConfig, i: int) -> list:
    """[(name, shape, kind)] of layer i's weights."""
    H, d, kvh = cfg.hidden_size, cfg.head_dim, cfg.num_key_value_heads
    nh = cfg.num_attention_heads_per_layer[i]
    out = [("ln1", (H,), "norm"), ("wq", (H, nh * d), "matrix"),
           ("wk", (H, kvh * d), "matrix"), ("wv", (H, kvh * d), "matrix"),
           ("wo", (nh * d, H), "matrix")]
    if cfg.gating:
        out.append(("wg", (H, nh * d), "matrix"))
    out.append(("ln2", (H,), "norm"))
    if not cfg.is_sparse(i):
        F = cfg.intermediate_size
        return out + [("gate", (H, F), "matrix"), ("up", (H, F), "matrix"),
                      ("down", (F, H), "matrix")]
    E, Fe = cfg.num_experts, cfg.moe_intermediate_size
    Fs = cfg.shared_expert_intermediate_size
    return out + [("router", (H, E), "matrix"),
                  ("e_gate", (E, H, Fe), "matrix"),
                  ("e_up", (E, H, Fe), "matrix"),
                  ("e_down", (E, Fe, H), "matrix"),
                  ("s_gate", (H, Fs), "matrix"), ("s_up", (H, Fs), "matrix"),
                  ("s_down", (Fs, H), "matrix")]


class LagunaForCausalLM(nn.Layer):
    """The decoder as ``LLMEngine`` takes it (``.config``,
    ``.parameters()``, ``decode_params()``).  Weights are drawn leaf by
    leaf in ``dtype`` itself; with ``materialize=False`` nothing is
    drawn or allocated."""

    def __init__(self, config: LagunaConfig, dtype="bfloat16",
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
        """Logits [B, T, V] of whole sequences, float32, no cache, the
        window as a mask on the full score matrix: the serving path's
        second opinion in the tests."""
        import jax
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        cfg = self.config
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                        self.decode_params())
        kvh, d, eps = cfg.num_key_value_heads, cfg.head_dim, cfg.rms_norm_eps
        rotary = {kind: cfg.rotary(kind) for kind in (FULL, SLIDING)}

        def one(toks):
            T = toks.shape[0]
            pos = jnp.arange(T)
            x = params["embed"][toks]
            for i, p in enumerate(params["layers"]):
                nh = cfg.num_attention_heads_per_layer[i]
                inv, scale = rotary[cfg.layer_types[i]]
                h = _rms_weight(x, p["ln1"], eps)
                q = rope_partial((h @ p["wq"]).reshape(T, nh, d), pos, inv,
                                 scale)
                k = rope_partial((h @ p["wk"]).reshape(T, kvh, d), pos, inv,
                                 scale)
                v = (h @ p["wv"]).reshape(T, kvh, d)
                see = pos[None, :] <= pos[:, None]
                if cfg.is_window(i):
                    see &= pos[None, :] > pos[:, None] - cfg.sliding_window
                qg = q.reshape(T, kvh, nh // kvh, d)
                s = jnp.einsum("qhgd,khd->hgqk", qg, k) / (d ** 0.5)
                s = jnp.where(see[None, None], s, -jnp.inf)
                att = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1),
                                 v).reshape(T, nh * d)
                if cfg.gating:
                    att = att * jax.nn.sigmoid(h @ p["wg"])
                x = x + att @ p["wo"]
                h2 = _rms_weight(x, p["ln2"], eps)
                if cfg.is_sparse(i):
                    x = x + moe_ffn(h2, p, cfg)[0]
                else:
                    x = x + swiglu(h2, p["gate"], p["up"], p["down"])
            return _rms_weight(x, params["norm_f"], eps) @ params["head"]

        with jax.default_matmul_precision("highest"):
            return Tensor(jnp.stack([one(t) for t in ids.astype(jnp.int32)]))
