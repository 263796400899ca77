"""The system under test, built as ``paddle_tpu/inference/frontend/
__main__.py`` builds it: the same calls as ``main`` and ``_build_engine``,
with the model that the configuration's architecture names
(``builders/<reference>.py``) and the weights handed in from the
benchmark's own draw.  This is the only harness file that imports the
program; the builders are the only other benchmark files that do."""
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


def build_model(cfg: dict, seed: int, split: dict):
    """The model of the architecture that the configuration's file names
    (``"reference"``): its builder constructs it in the served type, then
    every weight is replaced by the benchmark's draw from the seed (one
    jitted call over the leaves its shapes file lists)."""
    import jax
    import jax.numpy as jnp

    from . import spec, weights as W

    shapes = spec.load_shapes(cfg["reference"])
    builder = spec.load_builder(cfg["reference"])
    t = time.monotonic()
    model = builder.construct(cfg)
    jax.block_until_ready([p._data for p in model.parameters()])
    split["model_build_s"] = time.monotonic() - t

    t = time.monotonic()
    made = W.make_all(shapes.leaves(cfg), seed,
                      jnp.dtype(cfg.get("dtype", "bfloat16")))
    jax.block_until_ready(made)
    builder.place(model, made)
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
