"""The program's model for ``"reference": "llama_dense"``:
``LlamaForCausalLM`` as ``paddle_tpu/inference/frontend/__main__.py``
builds it, with the ``LlamaConfig`` taken from the configuration's file
(the CLI knows only its three presets), and each of its weights replaced
by the benchmark's draw.  With ``harness/server.py`` the only kind of
benchmark file that imports the program.

What a builder states: ``construct(cfg)``, the model that ``LLMEngine``
takes, in the served type; ``place(model, made)``, which hands it the
leaves ``harness/weights.py`` ``make_all`` drew from this architecture's
``shapes/`` file."""
from __future__ import annotations


def llama_config(cfg: dict):
    from paddle_tpu.models.llama import LlamaConfig
    return LlamaConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_hidden_layers=int(cfg["num_hidden_layers"]),
        num_attention_heads=int(cfg["num_attention_heads"]),
        num_key_value_heads=int(cfg["num_key_value_heads"]),
        max_position_embeddings=int(cfg["serving"]["max_model_len"]),
        rms_norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        tie_word_embeddings=False)


def construct(cfg: dict):
    import paddle_tpu
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle_tpu.seed(0)
    model = LlamaForCausalLM(llama_config(cfg))
    dtype = cfg.get("dtype", "bfloat16")
    if dtype != "float32":
        model.to(dtype=dtype)
    return model


def place(model, made: dict) -> None:
    place_body(model, made)
    model.lm_head.weight._data = made["top"]["head"]


def place_body(model, made: dict) -> None:
    """Everything below the output head."""
    m = model.model
    m.embed_tokens.weight._data = made["top"]["embed"]
    m.norm.weight._data = made["top"]["norm_f"]
    for lyr, w in zip(m.layers, made["layers"]):
        lyr.input_layernorm.weight._data = w["ln1"]
        lyr.self_attn.q_proj.weight._data = w["wq"]
        lyr.self_attn.k_proj.weight._data = w["wk"]
        lyr.self_attn.v_proj.weight._data = w["wv"]
        lyr.self_attn.o_proj.weight._data = w["wo"]
        lyr.post_attention_layernorm.weight._data = w["ln2"]
        lyr.mlp.gate_proj.weight._data = w["gate"]
        lyr.mlp.up_proj.weight._data = w["up"]
        lyr.mlp.down_proj.weight._data = w["down"]
