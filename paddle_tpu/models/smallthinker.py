"""A decoder whose layers alternate between global attention without
positions and sliding-window attention with rotary positions, every
layer with sparse ReGLU experts whose router reads the PRE-attention
norm (the SmallThinker family: ``sliding_window_layout``,
``rope_layout``, ``moe_num_primary_experts``,
``moe_num_active_primary_experts``, ``moe_ffn_hidden_size``,
``moe_primary_router_apply_softmax``).

One layer, ``h = RMSNorm(x; ln1)``:

*Router.*  ``s = W_r h`` in float32, taken from ``h``, the attention
block's input ("router placed before attention"); the
``moe_num_active_primary_experts`` largest, ``g = softmax`` over those
taken logits (``norm_topk_prob`` then changes nothing).

*Attention.*  ``q, k, v = h W_q, h W_k, h W_v`` as ``num_attention_heads``
/ ``num_key_value_heads`` heads of ``head_dim`` (the heads' total is not
the hidden size), no bias, no QK-norm.  Where ``rope_layout[i]`` is 1,
rotary (interleaved pairs) on q and k; where 0, none (NoPE).  Where
``sliding_window_layout[i]`` is 1, a query at position i sees keys
``i - sliding_window_size < j <= i``; where 0, every key up to its own.

*Experts.*  ``h2 = RMSNorm(x; ln2)``; ``x += sum over the chosen e of
g_e (relu(h2 G_e) * (h2 U_e)) D_e``.  No shared expert, no dense layer.

The serving engine keeps the two kinds of layer in two page pools with a
block table each (``inference/serving.py``): a window layer's pages
below a sequence's window go back to their pool.
``SmallThinkerForCausalLM.forward`` is the whole-sequence pass with no
cache, the window as a mask on the full score matrix.
"""
from __future__ import annotations

from dataclasses import dataclass, field

from .. import nn
from .llama import _rms_weight, _rope_positions
from .mla_moe import _Leaves, expert_counts, routed_experts, top_leaves


def _period4(n: int) -> list:
    return [0 if i % 4 == 0 else 1 for i in range(n)]


@dataclass
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    sliding_window_size: int = 4096
    # 1: a window layer / a rotary layer; the published model has both 0
    # at every fourth layer and 1 elsewhere.  Longer than the depth is
    # fine: a cut model reads its first num_hidden_layers entries
    sliding_window_layout: list = field(default_factory=lambda: _period4(52))
    rope_layout: list = field(default_factory=lambda: _period4(52))
    max_position_embeddings: int = 16384
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1.5e6

    architecture = "smallthinker"

    def __post_init__(self):
        n = self.num_hidden_layers
        if len(self.sliding_window_layout) < n or len(self.rope_layout) < n:
            raise ValueError(
                f"layouts of {len(self.sliding_window_layout)} and "
                f"{len(self.rope_layout)} entries for {n} layers")
        for i in range(n):
            if bool(self.sliding_window_layout[i]) != bool(
                    self.rope_layout[i]):
                raise ValueError(
                    f"layer {i}: a window layer without rotary positions, "
                    "or a global layer with them, is not a kind the step "
                    "programs have (layer_stack.ATTENTION)")

    @property
    def experts_held(self) -> int:
        """Every expert of a layer is held here."""
        return self.moe_num_primary_experts

    def is_window(self, i: int) -> bool:
        return bool(self.sliding_window_layout[i])

    def layer_kinds(self) -> list:
        """(attention kind, FFN kind) of every layer."""
        return [("gqa_window" if self.is_window(i) else "gqa_nope",
                 "moe_reglu") for i in range(self.num_hidden_layers)]

    @staticmethod
    def tiny(vocab=96, hidden=48, layers=8, heads=7, kv_heads=1,
             head_dim=16, experts=8, active=3, ffn=32, window=32, seq=256):
        return SmallThinkerConfig(
            vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
            num_attention_heads=heads, num_key_value_heads=kv_heads,
            head_dim=head_dim, moe_num_primary_experts=experts,
            moe_num_active_primary_experts=active, moe_ffn_hidden_size=ffn,
            sliding_window_size=window,
            sliding_window_layout=_period4(layers),
            rope_layout=_period4(layers), max_position_embeddings=seq)


# ---------------------------------------------------------------------------
# the layer's arithmetic
# ---------------------------------------------------------------------------

def route(h, p, cfg: SmallThinkerConfig):
    """(idx [T, k] expert ids, g [T, k] float32 gates) from h, the
    PRE-attention norm's output.  Logits in float32 (two experts a
    bfloat16 apart would otherwise change places); the gates are a
    softmax over the taken logits alone."""
    import jax
    import jax.numpy as jnp
    logits = jnp.dot(h, p["router"], preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(logits, cfg.moe_num_active_primary_experts)
    return idx, jax.nn.softmax(top, axis=-1)


def moe_ffn(h, h2, p, cfg: SmallThinkerConfig, valid=None,
            use_kernel=False):
    """The expert layer's contribution to x: routed by h (the attention
    block's input), computed on h2 (the FFN's).  Returns (out [T, H],
    counts int32 [4] as ``mla_moe.expert_counts``)."""
    import jax
    import jax.numpy as jnp
    if valid is None:
        valid = jnp.ones((h2.shape[0],), bool)
    with jax.named_scope("router"):
        idx, g = route(h, p, cfg)
    out, n_here, sizes = routed_experts(
        h2, idx, g, p, first=0, held=cfg.moe_num_primary_experts,
        valid=valid, use_kernel=use_kernel, gate="relu")
    return out, expert_counts(n_here, sizes, valid,
                              cfg.moe_num_active_primary_experts)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def layer_leaves(cfg: SmallThinkerConfig, i: int) -> list:
    """[(name, shape, kind)] of a layer's weights (every layer alike)."""
    H, d = cfg.hidden_size, cfg.head_dim
    nh, kvh = cfg.num_attention_heads, cfg.num_key_value_heads
    E, F = cfg.moe_num_primary_experts, cfg.moe_ffn_hidden_size
    return [("ln1", (H,), "norm"), ("router", (H, E), "matrix"),
            ("wq", (H, nh * d), "matrix"), ("wk", (H, kvh * d), "matrix"),
            ("wv", (H, kvh * d), "matrix"), ("wo", (nh * d, H), "matrix"),
            ("ln2", (H,), "norm"), ("e_gate", (E, H, F), "matrix"),
            ("e_up", (E, H, F), "matrix"), ("e_down", (E, F, H), "matrix")]


class SmallThinkerForCausalLM(nn.Layer):
    """The decoder as ``LLMEngine`` takes it (``.config``,
    ``.parameters()``, ``decode_params()``).  Weights are drawn leaf by
    leaf in ``dtype`` itself; with ``materialize=False`` nothing is
    drawn or allocated."""

    def __init__(self, config: SmallThinkerConfig, dtype="bfloat16",
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
        nh, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        eps = cfg.rms_norm_eps

        def one(toks):
            T = toks.shape[0]
            pos = jnp.arange(T)
            x = params["embed"][toks]
            for i, p in enumerate(params["layers"]):
                h = _rms_weight(x, p["ln1"], eps)
                q = (h @ p["wq"]).reshape(T, nh, d)
                k = (h @ p["wk"]).reshape(T, kvh, d)
                v = (h @ p["wv"]).reshape(T, kvh, d)
                see = pos[None, :] <= pos[:, None]
                if cfg.is_window(i):
                    q = _rope_positions(q, pos, cfg.rope_theta)
                    k = _rope_positions(k, pos, cfg.rope_theta)
                    see &= pos[None, :] > pos[:, None] \
                        - cfg.sliding_window_size
                qg = q.reshape(T, kvh, nh // kvh, d)
                s = jnp.einsum("qhgd,khd->hgqk", qg, k) / (d ** 0.5)
                s = jnp.where(see[None, None], s, -jnp.inf)
                att = jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(s, -1), v)
                x = x + att.reshape(T, nh * d) @ p["wo"]
                h2 = _rms_weight(x, p["ln2"], eps)
                x = x + moe_ffn(h, h2, p, cfg)[0]
            return _rms_weight(x, params["norm_f"], eps) @ params["head"]

        with jax.default_matmul_precision("highest"):
            return Tensor(jnp.stack([one(t) for t in ids.astype(jnp.int32)]))
