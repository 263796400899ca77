"""The program's model for ``"reference": "smallthinker"``:
``paddle_tpu.models.smallthinker.SmallThinkerForCausalLM``, constructed
without drawing or allocating a weight (the benchmark's draw is about to
be handed in, and at 7.9 GB there is room for the weights once), and
each of its leaves set to the benchmark's.  What a builder states is in
``builders/llama_dense.py``."""
from __future__ import annotations


def model_config(cfg: dict):
    from paddle_tpu.models.smallthinker import SmallThinkerConfig
    return SmallThinkerConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        num_hidden_layers=int(cfg["num_hidden_layers"]),
        num_attention_heads=int(cfg["num_attention_heads"]),
        num_key_value_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        moe_num_primary_experts=int(cfg["moe_num_primary_experts"]),
        moe_num_active_primary_experts=int(
            cfg["moe_num_active_primary_experts"]),
        moe_ffn_hidden_size=int(cfg["moe_ffn_hidden_size"]),
        sliding_window_size=int(cfg["sliding_window_size"]),
        sliding_window_layout=list(cfg["sliding_window_layout"]),
        rope_layout=list(cfg["rope_layout"]),
        max_position_embeddings=int(cfg["serving"]["max_model_len"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]))


def construct(cfg: dict):
    from paddle_tpu.models.smallthinker import SmallThinkerForCausalLM
    return SmallThinkerForCausalLM(model_config(cfg),
                                   dtype=cfg.get("dtype", "bfloat16"),
                                   materialize=False)


def place(model, made: dict) -> None:
    for name, a in made["top"].items():
        model.top._parameters[name]._data = a
    for lyr, w in zip(model.layers, made["layers"]):
        for name, a in w.items():
            lyr._parameters[name]._data = a
