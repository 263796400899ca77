"""Rehearsals: every cell's command end to end at a tiny size on the CPU,
through the child generator and the last line's parser; and the rest of
a run (the look for a chip skipped) with the timed path broken
underneath, which has to come out as not correct."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)

sys.path.insert(0, BENCH)
import run as bench_run  # noqa: E402
from harness import server, spec  # noqa: E402

REQUIRED = {"correct", "attempted", "failed", "metrics", "device"}


def _command(cell, trace, rehearsal, seed):
    bench = spec.load_benchmark()
    cmd = bench["command"] + ["--workload", cell, "--seed", str(seed),
                              "--seconds", "3", "--trace", str(trace),
                              "--rehearsal", rehearsal]
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    p = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p


@pytest.mark.parametrize("cell,trace", [
    ("mistral7b.chat", 0), ("yi6b.chat", 0), ("mistral7b.sysprompt", 0),
    ("mistral7b.sysprompt", 1)])
def test_cell_command_end_to_end_at_a_tiny_size(cell, trace):
    bench = spec.load_benchmark()
    reh = "rehearsal_sysprompt.json" if cell.endswith("sysprompt") \
        else "rehearsal.json"
    res, p = _command(cell, trace, os.path.join(HERE, "data", reh),
                      2**31 + 77)
    assert REQUIRED <= set(res) and list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"     # named for what it is
    group = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in spec.metrics_for(bench, group, cell)}
    assert set(res["metrics"]) <= names
    if not trace:
        assert set(res["metrics"]) == names
        assert all(m["value"] > 0 for m in res["metrics"].values())
    else:
        # no device trace on the CPU: its readers return nothing, never 0
        assert "attn.roofline_share" not in res["metrics"]
        assert "step.decode_ms" in res["metrics"]
        assert "frontend.ttft_p50_ms" not in res["metrics"]   # end to end here
        if cell.endswith("sysprompt"):
            assert res["metrics"]["engine.prefix_hit_share"]["value"] > 30
    # each number compared stands beside its limit, last on stderr too
    tail = p.stderr.strip().splitlines()[-7:]
    assert tail[-1] == "[bench] correct = True"
    assert any("served_gap_max" in l and "limit" in l for l in tail)


def test_no_chip_no_result():
    bench = spec.load_benchmark()
    cmd = bench["command"] + ["--workload", "mistral7b.chat", "--seed", "1",
                              "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


@pytest.fixture(scope="module")
def started():
    cache_dir, watch, device, _chip_start_s = server.start_jax()
    yield watch, device
    watch.close()


def _run(started, break_path, seed):
    watch, device = started
    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, "mistral7b.chat")
    with open(os.path.join(HERE, "data", "rehearsal.json")) as f:
        over = json.load(f)
    cfg = bench_run._overlay(spec.load_config(bench, cell["config"]),
                             over["config"])
    traffic = bench_run._overlay(spec.load_traffic(cell["traffic"]),
                                 over["traffic"])
    tf = bench_run.rehearsal_traffic_file(traffic)
    return bench_run.run_cell(bench, cell, cfg, tf, traffic, seed, 2.0, False,
                              device, watch, break_path=break_path)


def test_rest_of_a_run_is_correct_when_sound(started):
    res = _run(started, None, 11)
    assert res["correct"] is True
    assert res["compared"]["served_gap_max"]["value"] <= 1e-3


def test_a_token_altered_where_it_is_produced_is_not_correct(started):
    def break_path(engine):
        notify = engine._notify_tokens
        vocab = engine.config.vocab_size
        count = {"n": 0}

        def altered(req, toks):
            count["n"] += 1
            if count["n"] % 5 == 0:
                toks = tuple((int(t) + 1) % vocab for t in toks)
            return notify(req, toks)

        engine._notify_tokens = altered

    res = _run(started, break_path, 12)
    assert res["failed"] == 0                    # every stream still ends well
    assert res["correct"] is False
    c = res["compared"]["served_gap_max"]
    assert c["value"] > c["limit"]
