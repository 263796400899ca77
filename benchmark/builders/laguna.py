"""The program's model for ``"reference": "laguna"``:
``paddle_tpu.models.laguna.LagunaForCausalLM``, constructed without
drawing or allocating a weight (the benchmark's draw is about to be
handed in, and at 11.3 GB there is room for the weights once), and each
of its leaves set to the benchmark's.  What a builder states is in
``builders/llama_dense.py``."""
from __future__ import annotations


def model_config(cfg: dict):
    from paddle_tpu.models.laguna import LagunaConfig
    return LagunaConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_hidden_layers=int(cfg["num_hidden_layers"]),
        num_key_value_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        num_experts=int(cfg["num_experts"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        shared_expert_intermediate_size=int(
            cfg["shared_expert_intermediate_size"]),
        moe_routed_scaling_factor=float(cfg["moe_routed_scaling_factor"]),
        gating=bool(cfg["gating"]),
        sliding_window=int(cfg["sliding_window"]),
        layer_types=list(cfg["layer_types"]),
        num_attention_heads_per_layer=list(
            cfg["num_attention_heads_per_layer"]),
        mlp_layer_types=list(cfg["mlp_layer_types"]),
        rope_parameters={k: dict(v) for k, v in
                         cfg["rope_parameters"].items()
                         if isinstance(v, dict)},
        max_position_embeddings=int(cfg["serving"]["max_model_len"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]))


def construct(cfg: dict):
    from paddle_tpu.models.laguna import LagunaForCausalLM
    return LagunaForCausalLM(model_config(cfg),
                             dtype=cfg.get("dtype", "bfloat16"),
                             materialize=False)


def place(model, made: dict) -> None:
    for name, a in made["top"].items():
        model.top._parameters[name]._data = a
    for lyr, w in zip(model.layers, made["layers"]):
        for name, a in w.items():
            lyr._parameters[name]._data = a
