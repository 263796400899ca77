"""A configuration, a traffic mix (of a new kind), a cell, an architecture
(its plain reference, its shapes and counts, the builder of the program's
model) and a per-layer metric each arrive as new files and entries:
nothing that is there is edited.  Shown twice: at the loader, and by a
second architecture that goes through ``run.py --rehearsal`` end to end
(``data/tied_dense/``: the dense decoder with tied embedding and head,
which the program can run and a harness that spells out the untied one
could not build)."""
import ast
import hashlib
import json
import os
import shutil
import subprocess

import pytest

from harness import loadgen, spec

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)


def _tree(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture()
def copy(tmp_path, monkeypatch):
    dst = tmp_path / "repo"
    shutil.copytree(BENCH, dst / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst / "BENCHMARK.json")
    root = str(dst / "benchmark")
    monkeypatch.setattr(spec, "ROOT", root)
    monkeypatch.setattr(spec, "REPO", str(dst))
    monkeypatch.setattr(loadgen, "HERE", os.path.join(root, "harness"))
    return dst


def test_new_files_are_found_by_name_with_no_edit(copy):
    root = copy / "benchmark"
    before = _tree(root)
    (root / "configs" / "other-1b.json").write_text(json.dumps(
        {"name": "other-1b", "hidden_size": 2048, "reference": "other_arch",
         "experts": 32}))
    (root / "traffic" / "bursts.json").write_text(json.dumps(
        {"kind": "open_loop", "rate_per_s": 3.5}))
    (root / "harness" / "kinds" / "open_loop.py").write_text(
        "def drive(port, spec, vocab, seed, seconds, emit):\n"
        "    emit({'event': 'records', 'records': []})\n")
    (root / "references" / "other_arch.py").write_text(
        "def logits_at(*a, **k):\n    return 'other'\n")
    (root / "shapes" / "other_arch.py").write_text(
        "KERNELS = ('latent_attention',)\n"
        "def leaves(cfg):\n"
        "    return [('router_bias', 1, (cfg['experts'],), 'zero'),\n"
        "            ('experts_up', 1, (cfg['experts'], 4, 8), 'matrix')]\n")
    (root / "builders" / "other_arch.py").write_text(
        "def construct(cfg):\n    return 'model of ' + cfg['name']\n")
    assert "expert.load_share" not in {
        m["name"] for m in spec.load_benchmark()["per_layer"]}
    (root / "metrics" / "expert.load_share.py").write_text(
        "def read(ctx):\n    return ctx.get('x')\n")
    bench = spec.load_benchmark()
    bench["configs"].append({"name": "other-1b", "source": "paper",
                             "file": "benchmark/configs/other-1b.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "other.bursts", "config": "other-1b",
                               "traffic": "bursts", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "expert.load_share", "unit": "%",
                               "better": "lower", "source": "device_trace",
                               "layer": "matmuls", "moves": "out_tokens_per_s",
                               "workloads": ["other.bursts"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, "other.bursts")
    cfg = spec.load_config(bench, cell["config"], root=str(copy))
    assert cfg["hidden_size"] == 2048
    traffic = spec.load_traffic(cell["traffic"])
    assert traffic["rate_per_s"] == 3.5
    got = []
    loadgen.load_kind(traffic["kind"]).drive(0, traffic, 1, 1, 1, got.append)
    assert got == [{"event": "records", "records": []}]
    assert spec.load_reference(cfg["reference"]).logits_at() == "other"
    arch = spec.load_shapes(cfg["reference"])
    assert arch.KERNELS == ("latent_attention",)
    assert arch.leaves(cfg)[1] == ("experts_up", 1, (32, 4, 8), "matrix")
    assert spec.load_builder(cfg["reference"]).construct(cfg) \
        == "model of other-1b"
    assert spec.load_reader("expert.load_share")({"x": 4.5}) == 4.5
    assert spec.load_reader("expert.load_share")({}) is None
    per = [m["name"] for m in spec.metrics_for(bench, "per_layer",
                                               "other.bursts")]
    assert per == ["expert.load_share"]              # the rest list other cells
    assert "expert.load_share" not in [
        m["name"] for m in spec.metrics_for(bench, "per_layer",
                                            "mistral7b.chat")]
    after = _tree(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 7


def test_every_name_in_benchmark_json_has_its_file():
    bench = spec.load_benchmark()
    for c in bench["configs"]:
        cfg = spec.load_config(bench, c["name"])
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        spec.load_reference(cfg["reference"])
    for w in bench["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
        loadgen.load_kind(spec.load_traffic(w["traffic"])["kind"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "gap_p99_ms" not in e2e
    assert len(e2e & {"gap_p95_ms", "gap_top5_mean_ms"}) == 1
    with open(os.path.join(REPO, "PERF.md"), encoding="utf-8") as f:
        section3 = f.read().split("\n## 3.")[1].split("\n## 4.")[0]
    rows = [l.split("|")[1].strip() for l in section3.splitlines()
            if l.startswith("| ") and not l.startswith("| ---")][1:]
    layers = set()
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"]))
        assert m["moves"] in e2e
        layers.add(m["layer"])
    # a metric's layer is one of PERF.md's list of layers, letter for
    # letter; the list may name layers that have no metric yet
    assert layers and layers <= set(rows), (layers, rows)
    # every cell reports set-up, another end-to-end metric and a per-layer one
    for w in bench["workloads"]:
        e = [m["name"] for m in spec.metrics_for(bench, "end_to_end", w["name"])]
        assert "setup_s" in e and len(e) >= 3
        assert spec.metrics_for(bench, "per_layer", w["name"])
        for m in spec.metrics_for(bench, "per_layer", w["name"]):
            assert m["moves"] in e


# ---------------------------------------------------------------------------
# a second architecture, end to end
# ---------------------------------------------------------------------------

TIED = os.path.join(HERE, "data", "tied_dense")

# the tied model beside the untied architecture's leaves and reference:
# every weight the two share is the same weight, so what the comparison
# has to see is the head alone
UNTIED_FILES = {
    "shapes": "from harness import spec\n"
              "_dense = spec.load_shapes('llama_dense')\n"
              "def __getattr__(name):\n"
              "    return getattr(_dense, name)\n",
    "references": "from harness import spec\n"
                  "def logits_at(*a, **k):\n"
                  "    return spec.load_reference('llama_dense')"
                  ".logits_at(*a, **k)\n",
    "builders": "from harness import spec\n"
                "construct = spec.load_builder('tied_dense').construct\n"
                "def place(model, made):\n"
                "    del made['top']['head']\n"
                "    spec.load_builder('tied_dense').place(model, made)\n"}


def _add_tied_architecture(copy):
    """Files and entries, as a ``model_config`` PR would bring them."""
    root = copy / "benchmark"
    for kind, src in (("shapes", "shapes.py"), ("builders", "builder.py"),
                      ("references", "reference.py")):
        shutil.copy(os.path.join(TIED, src), root / kind / "tied_dense.py")
        (root / kind / "tied_as_untied.py").write_text(UNTIED_FILES[kind])
    with open(os.path.join(TIED, "config.json")) as f:
        cfg = json.load(f)
    (root / "configs" / "tied-tiny.json").write_text(json.dumps(cfg))
    (root / "configs" / "tied-tiny-untied-reference.json").write_text(
        json.dumps(dict(cfg, name="tied-tiny-untied-reference",
                        reference="tied_as_untied")))
    with open(os.path.join(HERE, "data", "rehearsal.json")) as f:
        (copy / "rehearsal.json").write_text(json.dumps(
            {"traffic": json.load(f)["traffic"]}))
    bench = spec.load_benchmark()
    for name in ("tied-tiny", "tied-tiny-untied-reference"):
        bench["configs"].append(
            {"name": name, "source": cfg["source"], "reduced": [],
             "file": f"benchmark/configs/{name}.json", "why": "test"})
    cells = {"tied.chat": "tied-tiny",
             "tied_as_untied.chat": "tied-tiny-untied-reference"}
    for cell, config in cells.items():
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": "chat", "chips": 1, "why": "t"})
    for m in bench["per_layer"]:
        if "mistral7b.chat" in m["workloads"]:
            m["workloads"] += list(cells)
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))


def _rehearse(copy, cell, trace):
    cmd = spec.load_benchmark()["command"] + [
        "--workload", cell, "--seed", str(2**31 + 5), "--seconds", "3",
        "--trace", str(trace), "--rehearsal", "rehearsal.json"]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    p = subprocess.run(cmd, cwd=str(copy), env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p


@pytest.mark.parametrize("cell,trace,correct", [
    ("tied.chat", 0, True), ("tied.chat", 1, True),
    ("tied_as_untied.chat", 0, False)])
def test_another_architecture_arrives_as_files_and_runs(copy, cell, trace,
                                                        correct):
    before = _tree(copy / "benchmark")
    _add_tied_architecture(copy)
    res, p = _rehearse(copy, cell, trace)
    assert res["failed"] == 0 and res["attempted"] > 4
    assert res["correct"] is correct, p.stderr[-2000:]
    gap = res["compared"]["served_gap_max"]
    if correct:
        assert gap["value"] <= 1e-4 < gap["limit"]      # float32 both sides
    else:
        # the model that was built multiplies by the embedding's rows,
        # the reference by a head of its own: every token is another
        assert gap["value"] > 100 * gap["limit"]
        assert res["compared"]["tokens_compared"]["value"] >= 8
    if trace:
        # the engine that served named its programs' instructions itself
        line = [l for l in p.stdout.splitlines()
                if l.startswith("[bench] program_scopes ")][0]
        built = json.loads(line[len("[bench] program_scopes "):])
        assert built and all(k.startswith("ragged_step_t") and n > 100
                             for k, n in built.items())
        assert "step.decode_ms" in res["metrics"]
        assert "attn.roofline_share" not in res["metrics"]   # no device trace
    else:
        assert set(res["metrics"]) == {"out_tokens_per_s", "gap_p95_ms",
                                       "setup_s"}
    after = _tree(copy / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 8


def test_the_tied_architecture_has_no_head_leaf_and_other_weights():
    """Why a harness that names the untied decoder's leaves could not
    build it: one leaf fewer at the top, every later index one lower."""
    import sys
    sys.path.insert(0, TIED)
    try:
        import shapes as tied
    finally:
        sys.path.remove(TIED)
        sys.modules.pop("shapes", None)
    with open(os.path.join(TIED, "config.json")) as f:
        cfg = json.load(f)
    dense = spec.load_shapes("llama_dense").leaves(cfg)
    got = tied.leaves(cfg)
    assert [l[0] for l in got if l[1] is None] == ["embed", "norm_f"]
    assert len(got) == len(dense) - 1
    assert got.index(("ln1", 0, (64,), "norm")) == 2 \
        and dense.index(("ln1", 0, (64,), "norm")) == 3


# ---------------------------------------------------------------------------
# the seam itself: one name finds three files, two of them program-free,
# and nothing else in the harness knows an architecture
# ---------------------------------------------------------------------------

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


@pytest.mark.parametrize("config", [
    c["name"] for c in spec.load_benchmark()["configs"]])
def test_a_configurations_architecture_finds_its_three_files(config):
    bench = spec.load_benchmark()
    name = spec.load_config(bench, config)["reference"]
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
    cfg = spec.load_config(bench, config)
    names = [(n, at) for n, at, _shape, _kind in arch.leaves(cfg)]
    assert len(set(names)) == len(names)
    assert {k for *_, k in arch.leaves(cfg)} \
        <= {"norm", "embedding", "matrix", "zero"}
    # the two that make the yardstick import nothing of the program
    for kind in ("references", "shapes"):
        assert "paddle_tpu" not in _imports(
            os.path.join(BENCH, kind, f"{name}.py")), kind
    assert "paddle_tpu" in _imports(
        os.path.join(BENCH, "builders", f"{name}.py"))


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
    words = ("LlamaConfig", "LlamaForCausalLM", "num_key_value_heads",
             "num_attention_heads", "intermediate_size", "hidden_size",
             "num_hidden_layers", "lm_head", "q_proj", '"wq"', '"kv_write"',
             '"sample"', "ragged_paged_attention\"")
    for path in _python_files("run.py") + _python_files("harness") \
            + _python_files("metrics"):
        with open(path, encoding="utf-8") as f:
            text = f.read()
        held = [w for w in words if w in text]
        assert not held, (os.path.relpath(path, BENCH), held)


def test_unknown_names_fail_loudly():
    bench = spec.load_benchmark()
    with pytest.raises(SystemExit):
        spec.find_cell(bench, "nope")
    with pytest.raises(SystemExit):
        spec.load_traffic("nope")
    with pytest.raises(SystemExit):
        spec.load_reader("nope")
    with pytest.raises(SystemExit):
        loadgen.load_kind("nope")
    with pytest.raises(SystemExit):
        spec.load_reference("nope")
    with pytest.raises(SystemExit):
        spec.load_shapes("nope")
    with pytest.raises(SystemExit):
        spec.load_builder("nope")
