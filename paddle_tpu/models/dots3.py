"""A latent-attention decoder whose FULL layers attend to a learned
selection of their keys and whose SLIDING-window layers keep a latent of
their own, as one chip of an expert-parallel deployment holds it (the
``dots3_note`` family: DeepSeek-V3's latent attention with a low-rank
query and its ``noaux_tc`` router, DeepSeek-V3.2's indexer
(``index_n_heads``, ``index_head_dim``, ``index_topk``), ``swa_*`` sizes
for the window layers, ``attention_gate_type`` ``headwise``).

Layer ``i``, ``h = RMSNorm(x; ln1)``; its kind is ``layer_types[i]``.

*Latent attention, both kinds* (sizes ``nh, r_q, d_c, d_n, d_r, d_v`` of
the kind: 128, 1024, 512, 128, 64, 128 full; 64, 1024, 1024, 192, 64,
128 sliding).  ``c_q = RMSNorm(h W_qa; q_a_norm)``; ``q = c_q W_qb`` as
``nh`` heads of ``[q_nope | q_rope]``; ``[c_kv | k_r] = h W_kva``; the
cached row is ``[RMSNorm(c_kv; kv_norm) | rot(k_r)]``; rotary on
``q_rope`` and ``k_r`` (interleaved pairs, the kind's theta, no
scaling).  Absorbed: ``q'_h = W_kvb,h^K^T q_nope_h``, ``score_h = (q'_h
. c + q_rope_h . k_r) (d_n + d_r)^-1/2``, ``out_h = W_kvb,h^V sum p c``.
A sliding layer's query at ``t`` sees ``t - sliding_window_size < s <=
t``.

*Indexer, full layers.*  ``qI = c_q WI_qb`` as ``index_n_heads`` heads
of ``index_head_dim``, the first ``d_r`` numbers of each rotated; ``kI =
LayerNorm(h WI_k; ik_norm, ik_bias)``, its first ``d_r`` numbers
rotated, cached beside the latent row; ``w = h WI_w``.  ``I[t, s] =
sum_j w[t, j] n^-1/2 d^-1/2 relu(qI[t, j] . kI[s])``; ``S_t`` = the
``min(t + 1, index_topk)`` positions ``s <= t`` of largest ``I[t, .]``
(of equal scores the lower position); the attention above runs over
``S_t`` only.

*Gate, both kinds.*  ``g = sigmoid(h W_g)``, one number a head; each
head's output times its ``g`` before ``W_o``.

*FFN.*  ``mla_moe``'s: layer 0 SwiGLU; after it a shared SwiGLU expert
and the routed experts HELD HERE (``experts_held`` of
``n_routed_experts``), sigmoid scores over all, the
``num_experts_per_tok`` largest of ``s + b``, gates normalised over the
taken and scaled by ``routed_scaling_factor``.

The functions the serving engine runs are ``mla_moe.mla_project`` /
``mla_output`` and ``index_project`` below, by the sizes
``attention_by_kind`` hands each kind (``inference/layer_stack.py``
``_latent``).  ``Dots3ForCausalLM.forward`` is the whole-sequence pass in
the expanded form, the selection as a literal top-k and a mask.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from types import SimpleNamespace

from .. import nn
from .laguna import rope_partial
from .llama import _rms_weight
from .mla_moe import _Leaves, moe_ffn, swiglu, top_leaves

FULL, SLIDING = "full_attention", "sliding_attention"


def _layer_types(n: int) -> list:
    """Two full layers, then (sliding x 3, full) repeated: the published
    46-layer list is ``_layer_types(46)``."""
    return [FULL if i == 0 or i % 4 == 1 else SLIDING for i in range(n)]


@dataclass
class Dots3Config:
    vocab_size: int = 152064
    hidden_size: int = 5120
    intermediate_size: int = 13824         # the leading dense layers' FFN
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 46
    first_k_dense_replace: int = 1
    # by layer; longer than the depth is fine: a cut model reads its
    # first num_hidden_layers entries
    layer_types: list = field(default_factory=lambda: _layer_types(46))
    # the full layers
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 80000000.0
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    # the sliding layers
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 50000.0
    sliding_window_size: int = 513         # holds the query's own position
    attention_gate_type: str = "headwise"
    swa_attention_gate_type: str = "headwise"
    n_routed_experts: int = 256            # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 524288
    rms_norm_eps: float = 1e-5
    # this chip's share: experts_held consecutive experts from
    # ep_rank * experts_held (None: all of them)
    experts_held: int | None = None
    ep_size: int = 1
    ep_rank: int = 0

    architecture = "dots3"

    def __post_init__(self):
        if len(self.layer_types) < self.num_hidden_layers:
            raise ValueError(f"layer_types has {len(self.layer_types)} "
                             f"entries for {self.num_hidden_layers} layers")
        if self.experts_held is None:
            self.experts_held = self.n_routed_experts // self.ep_size
        if self.experts_held * self.ep_size != self.n_routed_experts:
            raise ValueError(
                f"experts_held={self.experts_held} x ep_size={self.ep_size}"
                f" is not n_routed_experts={self.n_routed_experts}")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"ep_rank={self.ep_rank} outside "
                             f"ep_size={self.ep_size}")
        for name in ("attention_gate_type", "swa_attention_gate_type"):
            if getattr(self, name) not in ("headwise", None):
                raise ValueError(f"{name}={getattr(self, name)!r}: the "
                                 "gate served is one number a head")
        if self.index_head_dim < self.qk_rope_head_dim:
            raise ValueError("an index head holds the rotated numbers")

    # (with ``experts_held``, ``num_experts_per_tok`` and
    # ``routed_scaling_factor``: what ``mla_moe.moe_ffn`` reads)
    @property
    def first_expert(self) -> int:
        return self.ep_rank * self.experts_held

    def is_window(self, i: int) -> bool:
        return self.layer_types[i] == SLIDING

    def layer_kinds(self) -> list:
        """(attention kind, FFN kind) of every layer."""
        return [("mla_window" if self.is_window(i) else "mla_select",
                 "swiglu" if i < self.first_k_dense_replace else "moe")
                for i in range(self.num_hidden_layers)]

    def sizes(self, layer_type: str) -> SimpleNamespace:
        """One kind of layer's latent attention: its heads ``nh``, ranks
        ``rq`` and ``dc``, head sizes ``dn``, ``dr``, ``dv``, its rotary
        frequencies, softmax scale, window, gate and indexer: what a
        step program hands the latent body (``layer_stack._latent``) and
        what the functions of ``mla_moe`` read."""
        import numpy as np
        pre = "swa_" if layer_type == SLIDING else ""
        get = lambda name: getattr(self, pre + name)
        dn, dr = get("qk_nope_head_dim"), get("qk_rope_head_dim")
        theta = float(get("rope_theta"))
        inv = (theta ** (-np.arange(0, dr, 2, dtype=np.float64) / dr)
               ).astype(np.float32)
        index = None
        if layer_type == FULL:
            index = SimpleNamespace(
                nh=self.index_n_heads, d=self.index_head_dim,
                topk=self.index_topk, inv_freq=inv,
                scale=self.index_n_heads ** -0.5
                * self.index_head_dim ** -0.5, eps=self.rms_norm_eps)
        return SimpleNamespace(
            nh=get("num_attention_heads"), rq=get("q_lora_rank"),
            dc=get("kv_lora_rank"), dn=dn, dr=dr, dv=get("v_head_dim"),
            q_norm=False, eps=self.rms_norm_eps, inv_freq=inv,
            sm_scale=(dn + dr) ** -0.5,
            window=self.sliding_window_size if layer_type == SLIDING
            else None,
            gated=get("attention_gate_type") == "headwise", index=index)

    def attention_by_kind(self) -> dict:
        return {"mla_select": self.sizes(FULL),
                "mla_window": self.sizes(SLIDING)}

    @staticmethod
    def tiny(vocab=96, hidden=64, layers=5, experts=8, held=None, ep_size=1,
             ep_rank=0, seq=256, topk=8, window=5):
        return Dots3Config(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=layers,
            layer_types=_layer_types(layers), num_attention_heads=4,
            q_lora_rank=48, kv_lora_rank=128, qk_nope_head_dim=16,
            qk_rope_head_dim=32, v_head_dim=16, index_n_heads=3,
            index_head_dim=48, index_topk=topk, swa_num_attention_heads=2,
            swa_q_lora_rank=40, swa_kv_lora_rank=256,
            swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=32,
            swa_v_head_dim=16, sliding_window_size=window,
            n_routed_experts=experts, num_experts_per_tok=3,
            experts_held=held, ep_size=ep_size, ep_rank=ep_rank,
            max_position_embeddings=seq)


# ---------------------------------------------------------------------------
# the indexer's arithmetic
# ---------------------------------------------------------------------------

def _layer_norm(x, w, b, eps):
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    out = (xf - mu) / jnp.sqrt(var + eps) * w.astype(jnp.float32) \
        + b.astype(jnp.float32)
    return out.astype(x.dtype)


def index_project(h, c_q, p, ix, pos):
    """What the indexer needs of h [T, H] and the query latent c_q [T,
    r_q] at positions pos: the index heads' queries [T, n, d] and the
    key to cache [T, d], both with their first numbers rotated, and the
    heads' weights [T, n] float32 with the score's constant folded in."""
    import jax.numpy as jnp
    T = h.shape[0]
    q = rope_partial((c_q @ p["wi_q"]).reshape(T, ix.nh, ix.d), pos,
                     ix.inv_freq)
    k = _layer_norm(h @ p["wi_k"], p["ik_norm"], p["ik_bias"], ix.eps)
    k = rope_partial(k[:, None], pos, ix.inv_freq)[:, 0]
    w = (h @ p["wi_w"]).astype(jnp.float32) * ix.scale
    return q, k, w


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def layer_leaves(cfg: Dots3Config, i: int) -> list:
    """[(name, shape, kind)] of layer i's weights."""
    H = cfg.hidden_size
    a = cfg.sizes(cfg.layer_types[i])
    out = [("ln1", (H,), "norm"), ("wqa", (H, a.rq), "matrix"),
           ("q_a_norm", (a.rq,), "norm"),
           ("wqb", (a.rq, a.nh * (a.dn + a.dr)), "matrix"),
           ("wkva", (H, a.dc + a.dr), "matrix"),
           ("kv_norm", (a.dc,), "norm"),
           ("wkvb", (a.dc, a.nh * (a.dn + a.dv)), "matrix"),
           ("wo", (a.nh * a.dv, H), "matrix"), ("wg", (H, a.nh), "matrix")]
    if a.index is not None:
        ix = a.index
        out += [("wi_q", (a.rq, ix.nh * ix.d), "matrix"),
                ("wi_k", (H, ix.d), "matrix"), ("ik_norm", (ix.d,), "norm"),
                ("ik_bias", (ix.d,), "zero"), ("wi_w", (H, ix.nh), "matrix")]
    out.append(("ln2", (H,), "norm"))
    if i < cfg.first_k_dense_replace:
        F = cfg.intermediate_size
        return out + [("gate", (H, F), "matrix"), ("up", (H, F), "matrix"),
                      ("down", (F, H), "matrix")]
    Fe, E = cfg.moe_intermediate_size, cfg.experts_held
    Fs = Fe * cfg.n_shared_experts
    return out + [("router", (H, cfg.n_routed_experts), "matrix"),
                  ("router_bias", (cfg.n_routed_experts,), "zero"),
                  ("e_gate", (E, H, Fe), "matrix"),
                  ("e_up", (E, H, Fe), "matrix"),
                  ("e_down", (E, Fe, H), "matrix"),
                  ("s_gate", (H, Fs), "matrix"), ("s_up", (H, Fs), "matrix"),
                  ("s_down", (Fs, H), "matrix")]


class Dots3ForCausalLM(nn.Layer):
    """The decoder as ``LLMEngine`` takes it (``.config``,
    ``.parameters()``, ``decode_params()``).  Weights are drawn leaf by
    leaf in ``dtype`` itself; with ``materialize=False`` nothing is
    drawn or allocated."""

    def __init__(self, config: Dots3Config, dtype="bfloat16",
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

    def forward(self, input_ids, return_selected: bool = False):
        """Logits [B, T, V] of whole sequences, expanded form, float32,
        no cache, the window and the selection as masks on the full
        score matrix: the serving path's second opinion in the tests.
        ``return_selected``: also {layer: bool [B, T, T]}, each full
        layer's ``S_t`` as a mask."""
        import jax
        import jax.numpy as jnp

        from ..core.tensor import Tensor
        cfg = self.config
        ids = input_ids._data if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                        self.decode_params())
        eps = cfg.rms_norm_eps
        from .mla_moe import rope_at
        selected: dict = {}

        def one(toks):
            T = toks.shape[0]
            pos = jnp.arange(T)
            causal = pos[None, :] <= pos[:, None]
            x = params["embed"][toks]
            for i, (p, (_a, ffn)) in enumerate(zip(params["layers"],
                                                   cfg.layer_kinds())):
                a = cfg.sizes(cfg.layer_types[i])
                h = _rms_weight(x, p["ln1"], eps)
                c_q = _rms_weight(h @ p["wqa"], p["q_a_norm"], eps)
                q = (c_q @ p["wqb"]).reshape(T, a.nh, a.dn + a.dr)
                ckv = h @ p["wkva"]
                c = _rms_weight(ckv[:, :a.dc], p["kv_norm"], eps)
                kv = (c @ p["wkvb"]).reshape(T, a.nh, a.dn + a.dv)
                q_rope = rope_at(q[..., a.dn:], pos, a.inv_freq)
                k_rope = rope_at(ckv[:, None, a.dc:], pos, a.inv_freq)[:, 0]
                see = causal
                if a.window is not None:
                    see = see & (pos[None, :] > pos[:, None] - a.window)
                if a.index is not None:
                    qi, ki, w = index_project(h, c_q, p, a.index, pos)
                    score = jnp.einsum(
                        "tj,tjs->ts", w, jax.nn.relu(
                            jnp.einsum("tjd,sd->tjs", qi, ki)))
                    score = jnp.where(causal, score, -jnp.inf)
                    k = min(a.index.topk, T)
                    _, idx = jax.lax.top_k(score, k)
                    chosen = jnp.zeros((T, T), bool).at[
                        jnp.arange(T)[:, None], idx].set(True)
                    see = see & chosen
                    selected.setdefault(i, []).append(see)
                s = (jnp.einsum("qhd,khd->hqk", q[..., :a.dn],
                                kv[..., :a.dn])
                     + jnp.einsum("qhr,kr->hqk", q_rope, k_rope)) \
                    * a.sm_scale
                s = jnp.where(see[None], s, -jnp.inf)
                att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                                 kv[..., a.dn:])
                if a.gated:
                    att = att * jax.nn.sigmoid(h @ p["wg"])[..., None]
                x = x + att.reshape(T, a.nh * a.dv) @ p["wo"]
                h2 = _rms_weight(x, p["ln2"], eps)
                if ffn == "swiglu":
                    x = x + swiglu(h2, p["gate"], p["up"], p["down"])
                else:
                    x = x + moe_ffn(h2, p, cfg)[0]
            return _rms_weight(x, params["norm_f"], eps) @ params["head"]

        with jax.default_matmul_precision("highest"):
            logits = Tensor(jnp.stack(
                [one(t) for t in ids.astype(jnp.int32)]))
        if return_selected:
            return logits, {i: jnp.stack(v) for i, v in selected.items()}
        return logits
