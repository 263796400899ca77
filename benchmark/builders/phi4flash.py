"""The program's model for ``"reference": "phi4flash"``:
``paddle_tpu.models.phi4flash.Phi4FlashForCausalLM``, constructed without
drawing or allocating a weight (the benchmark's draw is about to be
handed in, and at 7.7 GB there is room for the weights once), and each
of its leaves set to the benchmark's.  The model holds a run's leaves
STACKED over the run's repeats, which is how ``shapes/phi4flash.py``
declares them: an array the harness drew is the array the step program
scans over, and nothing is stacked or copied afterwards.  The leaves
whose scale decides whether a state lives go through the shapes file's
``published`` first, as the reference's do.  What a builder states is in
``builders/llama_dense.py``."""
from __future__ import annotations


def model_config(cfg: dict):
    from paddle_tpu.models.phi4flash import Phi4FlashConfig
    a = cfg["assumed_sizes"]
    return Phi4FlashConfig(
        vocab_size=int(cfg["vocab_size"]),
        hidden_size=int(cfg["hidden_size"]),
        intermediate_size=int(cfg["intermediate_size"]),
        num_hidden_layers=int(cfg["num_hidden_layers"]),
        num_attention_heads=int(cfg["num_attention_heads"]),
        num_key_value_heads=int(cfg["num_key_value_heads"]),
        sliding_window=int(cfg["sliding_window"]),
        mb_per_layer=int(cfg["mb_per_layer"]),
        layer_norm_eps=float(cfg["layer_norm_eps"]),
        max_position_embeddings=int(cfg["serving"]["max_model_len"]),
        tie_word_embeddings=bool(cfg["tie_word_embeddings"]),
        d_state=int(a["d_state"]), d_conv=int(a["d_conv"]),
        expand=int(a["expand"]), dt_rank=int(a["dt_rank"]))


def construct(cfg: dict):
    from paddle_tpu.models.phi4flash import Phi4FlashForCausalLM
    return Phi4FlashForCausalLM(model_config(cfg),
                                dtype=cfg.get("dtype", "bfloat16"),
                                materialize=False)


def place(model, made: dict) -> None:
    import jax

    from harness import spec
    shapes = spec.load_shapes("phi4flash")
    # jitted, and for the mapped leaves alone: the embedding's map is
    # one fused pass over 1 GB (no float32 copy of it beside the
    # weights), and a leaf handed on as drawn is the harness's own array
    # (through a jit it would come back as a COPY, the drawn one still
    # held: 7.7 GB twice, my chip runs, PR 42)
    mapped = jax.jit(shapes.published, static_argnums=0)

    def published(name, a):
        # (a shape in an array's place has nothing to map)
        if name in shapes.PUBLISHED and hasattr(a, "astype"):
            return mapped(name, a)
        return a

    for name, a in made["top"].items():
        model.top._parameters[name]._data = published(name, a)
    for row, leaves in zip(model.groups, made["layers"]):
        for tagged, a in leaves.items():
            k, name = tagged.split(".", 1)
            row[int(k)]._parameters[name]._data = published(name, a)
