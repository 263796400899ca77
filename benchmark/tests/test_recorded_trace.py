"""The trace reduction against a small recorded trace: one decode-only
step of mistral7b.sysprompt on the chip (tests/data/recorded_step.json).
The numbers asserted were read off the recording by hand once."""
import json
import os

import pytest

from harness import scopes, spec, xplane as X

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def rec():
    with open(os.path.join(HERE, "data", "recorded_step.json")) as f:
        d = json.load(f)
    d["events"] = [dict(zip(d["fields"], r)) for r in d["events"]]
    return d


def test_busy_idle_and_window(rec):
    w0, w1 = rec["window"]
    evs = X.clip(rec["events"], w0, w1)
    assert len(rec["events"]) == 1027 and len(evs) == 1020   # seven of no length
    assert X.busy_ns(evs) == 143750309
    gaps = X.idle_gaps(evs, w0, w1)
    assert gaps[0] == (0, 18759000)              # the host's turn
    assert sum(b - a for a, b in gaps) == (w1 - w0) - 143750309
    idle_share = 1 - X.busy_ns(evs) / (w1 - w0)
    assert idle_share == pytest.approx(0.1154, abs=1e-3)


def test_every_nanosecond_counted_once(rec):
    st = X.self_times(rec["events"])
    assert sum(e["self_ns"] for e in st) == X.busy_ns(rec["events"])
    scan = max((e for e in st if e["category"] == "while"),
               key=lambda e: e["dur_ns"])          # the scan over the layers
    assert scan["dur_ns"] == 112385818
    assert scan["self_ns"] == 1104                # its body is counted apart


def test_attention_kernel_of_a_program_that_does_not_name_it(rec):
    """This recording is older than the kernel's name (PR 25): its eight
    launches, one a layer, are ``closed_call`` custom calls, which the
    breakdown's label still tells apart; the readers, which go by the
    names the architecture's file lists, find nothing to read in it and
    say so (``test_scopes.py`` has the recording with the name)."""
    st = X.self_times(rec["events"])
    att = [e for e in st
           if X.label(e) == "closed_call custom-call bf16[32,8,4,128]"]
    assert len(att) == 8                          # one launch a layer
    assert sum(e["self_ns"] for e in att) == 80814608
    share = 80814608 / X.busy_ns(rec["events"])
    assert share == pytest.approx(0.562, abs=1e-3)
    arch = spec.load_shapes("llama_dense")
    assert scopes.kernel_ns(st, arch) == 0
    ctx = {"trace": {"events": st, "busy_s": X.busy_ns(st) / 1e9},
           "arch": arch}
    assert spec.load_reader("attn.device_share")(ctx) is None
    assert spec.load_reader("attn.roofline_share")(ctx) is None


def test_breakdown_labels(rec):
    lab = X.by_label(X.self_times(rec["events"]))
    top = sorted(lab.items(), key=lambda kv: -kv[1])[:3]
    assert [k for k, _ in top] == ["closed_call custom-call bf16[32,8,4,128]",
                                   "fusion pred[1048576]",
                                   "fusion f32[1048576]"]
    # the two whole-pool copies that close every step
    assert lab["copy bf16[8,4097,8,16,128]"] == 6459473


def test_gap_goes_to_the_host_span_that_covers_it(rec):
    w0, w1 = rec["window"]
    gaps = [g for g in X.idle_gaps(rec["events"], w0, w1)
            if g[1] - g[0] > 1_000_000]
    assert gaps == [(0, 18759000)]
    host = [{"name": "engine.schedule", "ts": 5_000_000, "dur": 14_000_000},
            {"name": "engine.sample_commit", "ts": 3_000_000,
             "dur": 2_000_000}]
    out = X.attribute_gaps(gaps, host, offset_ns=-2_000_000)
    assert out == {"engine.schedule": 18759000}
