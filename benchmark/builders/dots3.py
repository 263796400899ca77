"""The program's model for ``"reference": "dots3"``:
``paddle_tpu.models.dots3.Dots3ForCausalLM``, told which experts it
holds, constructed without drawing or allocating a weight (the
benchmark's draw is about to be handed in, and at 8.2 GB there is room
for the weights once), and each of its leaves set to the benchmark's.
What a builder states is in ``builders/llama_dense.py``."""
from __future__ import annotations


def model_config(cfg: dict):
    from paddle_tpu.models.dots3 import Dots3Config
    ep = cfg["expert_parallel"]
    same = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
            "swa_num_attention_heads", "swa_q_lora_rank",
            "swa_kv_lora_rank", "swa_qk_nope_head_dim",
            "swa_qk_rope_head_dim", "swa_v_head_dim", "sliding_window_size",
            "num_experts_per_tok", "n_shared_experts")
    return Dots3Config(
        **{k: int(cfg[k]) for k in same},
        layer_types=list(cfg["layer_types"]),
        rope_theta=float(cfg["rope_theta"]),
        swa_rope_theta=float(cfg["swa_rope_theta"]),
        attention_gate_type=cfg["attention_gate_type"],
        swa_attention_gate_type=cfg["swa_attention_gate_type"],
        n_routed_experts=int(ep["router_width"]),
        experts_held=int(cfg["n_routed_experts"]),
        ep_size=int(ep["ep_size"]), ep_rank=int(ep["ep_rank"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        max_position_embeddings=int(cfg["serving"]["max_model_len"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]))


def construct(cfg: dict):
    from paddle_tpu.models.dots3 import Dots3ForCausalLM
    return Dots3ForCausalLM(model_config(cfg),
                            dtype=cfg.get("dtype", "bfloat16"),
                            materialize=False)


def place(model, made: dict) -> None:
    for name, a in made["top"].items():
        model.top._parameters[name]._data = a
    for lyr, w in zip(model.layers, made["layers"]):
        for name, a in w.items():
            lyr._parameters[name]._data = a
