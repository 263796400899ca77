"""The system under test, built as ``paddle_tpu/inference/frontend/
__main__.py`` builds it: the same calls as ``main`` and ``_build_engine``,
with the ``LlamaConfig`` taken from the configuration's file (the CLI
knows only its three presets) and the weights handed in from the
benchmark's own draw.  This is the only harness file that imports the
program."""
from __future__ import annotations

import time


def start_jax():
    """What ``main`` does before first device use.  Returns (cache_dir,
    CompileWatch, device dict, seconds JAX took to start the chip's
    runtime: the one call that first touches the backend)."""
    from paddle_tpu.core.runtime import (CompileWatch,
                                         configure_compile_cache,
                                         resolve_device)
    cache_dir = configure_compile_cache()
    import jax
    # every program goes to the persistent cache, the small ones too: a
    # later run of this cell then compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    watch = CompileWatch()
    t = time.monotonic()
    device = resolve_device()
    return cache_dir, watch, device, time.monotonic() - t


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
        tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)))


def build_model(cfg: dict, seed: int, split: dict):
    """``LlamaForCausalLM(cfg)`` in the served type, then every weight
    replaced by the benchmark's draw from the seed (one jitted call)."""
    import jax

    import paddle_tpu
    from paddle_tpu.models.llama import LlamaForCausalLM

    from . import weights as W

    t = time.monotonic()
    paddle_tpu.seed(0)
    model = LlamaForCausalLM(llama_config(cfg))
    dtype = cfg.get("dtype", "bfloat16")
    if dtype != "float32":
        model.to(dtype=dtype)
    jax.block_until_ready([p._data for p in model.parameters()])
    split["model_build_s"] = time.monotonic() - t

    t = time.monotonic()
    import jax.numpy as jnp
    made = W.make_all(cfg, seed, jnp.dtype(dtype))
    jax.block_until_ready(made)
    m = model.model
    m.embed_tokens.weight._data = made["top"]["embed"]
    m.norm.weight._data = made["top"]["norm_f"]
    model.lm_head.weight._data = made["top"]["head"]
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
    split["weights_s"] = time.monotonic() - t
    return model


def build_engine(cfg: dict, model, split: dict):
    """The call ``_build_engine`` makes, at the CLI's defaults except for
    what the configuration's ``serving`` block states."""
    import jax

    from paddle_tpu.inference.serving import LLMEngine

    s = cfg["serving"]
    t = time.monotonic()
    engine = LLMEngine(
        model, max_num_seqs=int(s["max_num_seqs"]),
        block_size=int(s["block_size"]),
        num_blocks=int(s["num_blocks"]) if s.get("num_blocks") else None,
        max_model_len=int(s["max_model_len"]),
        max_prefill_tokens=int(s["max_prefill_tokens"]),
        enable_prefix_caching=bool(s["enable_prefix_caching"]),
        drafter=None, spec_k=0, kv_dtype="float32", weight_dtype="float32",
        tp=1, retain_outputs=False, kv_tier=None,
        devices=jax.devices()[:1])
    jax.block_until_ready(engine.params)
    split["engine_build_s"] = time.monotonic() - t
    return engine


def start_frontend(engine, name: str, watch, tracer=None):
    """``ServingFrontend`` with the CLI's defaults, served from a thread
    of this process (the process that holds the chip)."""
    from paddle_tpu.inference.frontend.app import (BackgroundServer,
                                                   ServingFrontend)
    frontend = ServingFrontend(
        engine, model_name=name, host="127.0.0.1", port=0,
        max_pending=None, default_deadline_s=None,
        slo_config={"ttft_p95_ms": 500.0, "itl_p99_ms": 200.0,
                    "deadline_attainment": 0.99, "availability": 0.999},
        flight_capacity=512, tracer=tracer, compile_watch=watch)
    return BackgroundServer(frontend)


def new_tracer():
    from paddle_tpu.profiler.trace import Tracer
    return Tracer(capacity=1 << 20)


def counters(engine) -> dict:
    """The counts of ``summary()`` (its numbers, as they stand now)."""
    return {k: v for k, v in engine.summary().items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}
