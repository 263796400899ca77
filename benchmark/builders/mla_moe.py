"""The program's model for ``"reference": "mla_moe"``:
``paddle_tpu.models.mla_moe.MlaMoeForCausalLM``, told which experts it
holds, constructed without drawing or allocating a weight (the
benchmark's draw is about to be handed in, and at 10.9 GB there is room
for the weights once), and each of its leaves set to the benchmark's.
What a builder states is in ``builders/llama_dense.py``."""
from __future__ import annotations


def model_config(cfg: dict):
    from paddle_tpu.models.mla_moe import MlaMoeConfig
    ep = cfg["expert_parallel"]
    return MlaMoeConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_hidden_layers=int(cfg["num_hidden_layers"]),
        first_k_dense_replace=int(cfg["first_k_dense_replace"]),
        num_attention_heads=int(cfg["num_attention_heads"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        num_experts=int(ep["router_width"]),
        experts_held=int(cfg["num_experts"]),
        ep_size=int(ep["ep_size"]), ep_rank=int(ep["ep_rank"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        num_shared_experts=int(cfg["num_shared_experts"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        max_position_embeddings=int(cfg["serving"]["max_model_len"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        rope_scaling=dict(cfg["rope_scaling"]))


def construct(cfg: dict):
    from paddle_tpu.models.mla_moe import MlaMoeForCausalLM
    return MlaMoeForCausalLM(model_config(cfg),
                             dtype=cfg.get("dtype", "bfloat16"),
                             materialize=False)


def place(model, made: dict) -> None:
    for name, a in made["top"].items():
        model.top._parameters[name]._data = a
    for lyr, w in zip(model.layers, made["layers"]):
        for name, a in w.items():
            lyr._parameters[name]._data = a
