"""A configuration, a traffic mix (of a new kind), a cell, a reference
and a per-layer metric each arrive as new files and entries: nothing
that is there is edited."""
import hashlib
import json
import os
import shutil

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
        {"name": "other-1b", "hidden_size": 2048, "reference": "other_arch"}))
    (root / "traffic" / "bursts.json").write_text(json.dumps(
        {"kind": "open_loop", "rate_per_s": 3.5}))
    (root / "harness" / "kinds" / "open_loop.py").write_text(
        "def drive(port, spec, vocab, seed, seconds, emit):\n"
        "    emit({'event': 'records', 'records': []})\n")
    (root / "references" / "other_arch.py").write_text(
        "def logits_at(*a, **k):\n    return 'other'\n")
    (root / "metrics" / "matmul.device_share.py").write_text(
        "def read(ctx):\n    return ctx.get('x')\n")
    bench = spec.load_benchmark()
    bench["configs"].append({"name": "other-1b", "source": "paper",
                             "file": "benchmark/configs/other-1b.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "other.bursts", "config": "other-1b",
                               "traffic": "bursts", "chips": 1, "why": "t"})
    bench["per_layer"].append({"name": "matmul.device_share", "unit": "%",
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
    assert spec.load_reader("matmul.device_share")({"x": 4.5}) == 4.5
    assert spec.load_reader("matmul.device_share")({}) is None
    per = [m["name"] for m in spec.metrics_for(bench, "per_layer",
                                               "other.bursts")]
    assert "matmul.device_share" in per
    assert "engine.prefix_hit_share" not in per      # lists other cells
    assert "matmul.device_share" not in [
        m["name"] for m in spec.metrics_for(bench, "per_layer",
                                            "mistral7b.chat")]
    after = _tree(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 5


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
    layers = set()
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"]))
        assert m["moves"] in e2e
        layers.add(m["layer"])
    assert len(layers) == 5
    # every cell reports set-up, another end-to-end metric and a per-layer one
    for w in bench["workloads"]:
        e = [m["name"] for m in spec.metrics_for(bench, "end_to_end", w["name"])]
        assert "setup_s" in e and len(e) >= 3
        assert spec.metrics_for(bench, "per_layer", w["name"])
        for m in spec.metrics_for(bench, "per_layer", w["name"]):
            assert m["moves"] in e


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
