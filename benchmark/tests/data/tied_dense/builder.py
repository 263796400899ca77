"""The program's model of the tied dense decoder: ``LlamaForCausalLM``
with ``tie_word_embeddings`` (no ``lm_head``).  A test's architecture."""
import dataclasses

from harness import spec

_dense = spec.load_builder("llama_dense")


def construct(cfg: dict):
    import paddle_tpu
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle_tpu.seed(0)
    model = LlamaForCausalLM(dataclasses.replace(
        _dense.llama_config(cfg), tie_word_embeddings=True))
    assert model.lm_head is None
    if cfg.get("dtype", "bfloat16") != "float32":
        model.to(dtype=cfg["dtype"])
    return model


def place(model, made: dict) -> None:
    assert "head" not in made["top"]
    _dense.place_body(model, made)
