"""(h) The tier-1 copy of the harness seam's tests (``benchmark/tests/
test_loader.py``, which the tier-1 command does not collect): every
configuration's architecture name finds its three files, only the server
and the builders import the program, the harness names no architecture,
and an unknown name fails loudly.  A change to ``paddle_tpu/`` that
breaks a builder, or a benchmark file that reaches into the program, now
fails here."""
import ast
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import loadgen, spec                            # noqa: E402


def _imports(path):
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            out.add((node.module or "").split(".")[0])
    return out


def _python_files(*parts):
    top = os.path.join(BENCH, *parts)
    if os.path.isfile(top):
        return [top]
    return sorted(os.path.join(d, f) for d, _dirs, files in os.walk(top)
                  for f in files if f.endswith(".py"))


CONFIGS = [c["name"] for c in spec.load_benchmark()["configs"]]


@pytest.mark.parametrize("config", CONFIGS)
def test_a_configurations_architecture_finds_its_three_files(config):
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, config)
    name = cfg["reference"]
    reference = spec.load_reference(name)
    arch = spec.load_shapes(name)
    builder = spec.load_builder(name)
    assert callable(reference.logits_at)
    assert callable(builder.construct) and callable(builder.place)
    for attr in ("dims", "leaves", "pool_shapes", "step_matmuls",
                 "attention_row"):
        assert callable(getattr(arch, attr)), attr
    for attr in ("KERNELS", "SCOPES", "MATMUL_SCOPES", "SAMPLE_SCOPES",
                 "POOL_SCOPES"):
        assert isinstance(getattr(arch, attr), tuple), attr
    assert isinstance(arch.LOOP, str)
    assert set(arch.MATMUL_SCOPES + arch.SAMPLE_SCOPES + arch.POOL_SCOPES) \
        <= set(arch.SCOPES)
    # the page writes first, then the kernels: ``kvwrite.device_share``
    # reads the first, the scope ``layer_stack`` writes a step's rows under
    assert arch.POOL_SCOPES[0] == "kv_write"
    names = [(n, at) for n, at, _shape, _kind in arch.leaves(cfg)]
    assert len(set(names)) == len(names)
    assert {k for *_, k in arch.leaves(cfg)} \
        <= {"norm", "embedding", "matrix", "zero"}
    for kind in ("references", "shapes"):
        assert "paddle_tpu" not in _imports(
            os.path.join(BENCH, kind, f"{name}.py")), kind
    assert "paddle_tpu" in _imports(
        os.path.join(BENCH, "builders", f"{name}.py"))


@pytest.mark.parametrize("config", CONFIGS)
def test_the_builders_model_has_the_shapes_files_leaves(config):
    """The program's model, as the builder constructs it, holds exactly
    the leaves the shapes file lists, each of its shape: what ``place``
    hands over fits, whatever the program's files call things."""
    import jax
    bench = spec.load_benchmark()
    cfg = spec.load_config(bench, config)
    # the published widths at a depth and a vocabulary that the CPU holds
    cfg = dict(cfg, num_hidden_layers=2, vocab_size=512)
    arch = spec.load_shapes(cfg["reference"])
    builder = spec.load_builder(cfg["reference"])
    if cfg["reference"] == "llama_dense":
        cfg.update(hidden_size=64, intermediate_size=128,
                   num_attention_heads=4, num_key_value_heads=2)
    elif cfg["reference"] == "mla_moe":
        cfg.update(num_experts=2, expert_parallel=dict(
            cfg["expert_parallel"], router_width=8))
    elif cfg["reference"] == "phi4flash":
        # the least depth that holds every kind of its layers
        cfg.update(num_hidden_layers=12)
    # (any other: the builder draws and allocates nothing, so the
    # published widths stay as they are)
    model = builder.construct(cfg)
    want = sorted(tuple(shape) for _n, _at, shape, _k in arch.leaves(cfg))
    got = sorted(tuple(p._data.shape) for p in model.parameters())
    assert got == want
    # (a leaf's tag is its layer, or a run of the stack whose leaves
    # are declared stacked)
    tags = 1 + max(at for _n, at, _s, _k in arch.leaves(cfg)
                   if at is not None)
    made = {"top": {}, "layers": [{} for _ in range(tags)]}
    for n, at, shape, _k in arch.leaves(cfg):
        (made["top"] if at is None else made["layers"][at])[n] = \
            jax.ShapeDtypeStruct(shape, "float32")
    builder.place(model, made)
    assert all(p._data.dtype == "float32" for p in model.parameters())


def test_only_the_server_and_the_builders_import_the_program():
    importers = {os.path.relpath(p, BENCH) for p in _python_files()
                 if os.sep + "tests" + os.sep not in p
                 and "paddle_tpu" in _imports(p)}
    assert importers == {os.path.join("harness", "server.py")} | {
        os.path.relpath(p, BENCH) for p in _python_files("builders")}


def test_the_harness_names_no_architecture():
    """``run.py``, ``harness/`` and ``metrics/`` hold no leaf, no
    dimension key and no class of any one architecture: those live in the
    three files its name finds."""
    words = ("LlamaConfig", "LlamaForCausalLM", "MlaMoe",
             "num_key_value_heads", "num_attention_heads",
             "intermediate_size", "hidden_size", "num_hidden_layers",
             "kv_lora_rank", "num_experts", "lm_head", "q_proj", '"wq"',
             '"kv_write"', '"sample"', '"moe_experts"',
             "ragged_paged_attention\"", "ragged_latent_attention")
    for path in _python_files("run.py") + _python_files("harness") \
            + _python_files("metrics"):
        with open(path, encoding="utf-8") as f:
            text = f.read()
        held = [w for w in words if w in text]
        assert not held, (os.path.relpath(path, BENCH), held)


def test_unknown_names_fail_loudly():
    bench = spec.load_benchmark()
    for find, arg in ((spec.find_cell, (bench, "nope")),
                      (spec.load_traffic, ("nope",)),
                      (spec.load_reader, ("nope",)),
                      (loadgen.load_kind, ("nope",)),
                      (spec.load_reference, ("nope",)),
                      (spec.load_shapes, ("nope",)),
                      (spec.load_builder, ("nope",))):
        with pytest.raises(SystemExit):
            find(*arg)


def test_every_cells_metrics_have_readers_and_the_cell_reports_them():
    bench = spec.load_benchmark()
    for cell in bench["workloads"]:
        e2e = {m["name"] for m in
               spec.metrics_for(bench, "end_to_end", cell["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        per = spec.metrics_for(bench, "per_layer", cell["name"])
        assert per
        for m in per:
            assert callable(spec.load_reader(m["name"]))
            assert m["moves"] in e2e
