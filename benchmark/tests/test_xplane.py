"""The trace reduction on hand-made records (the recorded trace has a
test of its own in test_recorded_trace.py)."""
from harness import scopes, spans as S, spec, xplane as X


def ev(name, start, dur, cat="", shape=""):
    return {"name": name, "start_ns": start, "dur_ns": dur,
            "category": cat, "shape": shape}


def test_busy_is_the_union_and_idle_its_complement():
    evs = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("d", 31, 2)]
    assert X.busy_intervals(evs) == [[0, 15], [30, 35]]
    assert X.busy_ns(evs) == 20
    assert X.idle_gaps(evs, 0, 50) == [(15, 30), (35, 50)]
    assert X.idle_gaps(evs, 10, 32) == [(15, 30)]


def test_clip_to_the_window():
    evs = X.clip([ev("a", 0, 10), ev("b", 20, 10), ev("c", 40, 5)], 5, 25)
    assert [(e["start_ns"], e["dur_ns"]) for e in evs] == [(5, 5), (20, 5)]


def test_self_time_counts_every_nanosecond_once():
    # a while loop of 100 ns holding two body operations of 30 and 40
    evs = [ev("while.3", 0, 100), ev("fusion.1", 10, 30),
           ev("custom-call.2", 50, 40), ev("copy.9", 120, 10)]
    st = {e["name"]: e["self_ns"] for e in X.self_times(evs)}
    assert st == {"while.3": 30, "fusion.1": 30, "custom-call.2": 40,
                  "copy.9": 10}
    assert sum(st.values()) == X.busy_ns(evs)


def test_labels_survive_renumbering():
    a = X.label(ev("%fusion.13", 0, 1, "fusion", "f32[32,32768]"))
    b = X.label(ev("fusion.977", 0, 1, "fusion", "f32[32,32768]"))
    assert a == b == "fusion f32[32,32768]"
    assert X.label(ev("copy.137", 0, 1)) == "copy"
    assert X.label(ev("closed_call.14", 0, 1, "custom-call",
                      "bf16[32,8,4,128]")) \
        == "closed_call custom-call bf16[32,8,4,128]"


def test_instruction_text_as_the_tpu_trace_names_it():
    t = ("%fusion.199 = (f32[192]{0:T(256)S(1)}, bf16[192,4096]{1,0:T(8,128)"
         "(2,1)S(1)}) fusion(bf16[192,4096]{1,0:T(8,128)(2,1)S(1)} "
         "%custom-call.42, s32[]{:T(128)} %g), kind=kOutput, calls=%fc.44")
    assert X.parse_instruction(t) == ("fusion.199", "fusion",
                                      "(f32[192],bf16[192,4096])")
    k = ("%closed_call.14 = bf16[192,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} "
         "custom-call(bf16[1]{0} %x), custom_call_target=\"tpu_custom_call\"")
    assert X.parse_instruction(k) == ("closed_call.14", "custom-call",
                                      "bf16[192,8,4,128]")
    assert X.parse_instruction("bench.mark") == ("bench.mark", "", "")


def test_attention_kernel_by_the_names_the_architecture_lists():
    arch = spec.load_shapes("llama_dense")
    evs = X.self_times([
        ev("ragged_paged_attention.14", 0, 5, "custom-call",
           "bf16[192,8,4,128]"),
        # whatever its result's shape: the other model's head layout
        ev("ragged_paged_attention_q8.2", 10, 7, "custom-call",
           "bf16[192,4,8,128]"),
        # a kernel without the name is not found, nor a fusion that
        # gives the kernel's shape, nor a name that only starts alike
        ev("closed_call.14", 20, 1, "custom-call", "bf16[192,8,4,128]"),
        ev("fusion.1", 30, 1, "fusion", "bf16[192,8,4,128]"),
        ev("ragged_paged_attention_bwd.1", 40, 1, "custom-call", "")])
    assert [scopes.is_kernel_name(e["name"], arch) for e in evs] \
        == [True, True, False, False, False]
    assert scopes.kernel_ns(evs, arch) == 12


def test_gaps_go_to_what_the_host_was_doing():
    host = [{"name": "engine.schedule", "ts": 100, "dur": 50},
            {"name": "engine.block_on_result", "ts": 150, "dur": 500},
            {"name": "engine.pack", "ts": 110, "dur": 10}]
    # device clock = host clock + 1000
    gaps = [(1100, 1150), (1112, 1118), (5000, 5010)]
    out = X.attribute_gaps(gaps, host, 1000)
    assert out == {"engine.schedule": 50, "engine.pack": 6,
                   "unattributed": 10}


def test_launches_join_dispatch_to_device_time():
    sp = [{"ph": "X", "name": "engine.dispatch", "ts": 0, "dur": 5,
           "args": {"launched": True, "chunks": 2, "decode": 7}},
          {"ph": "X", "name": "engine.device_inflight", "ts": 4,
           "dur": 600_000_000, "args": {"rows": 9}},
          {"ph": "X", "name": "engine.dispatch", "ts": 700_000_000, "dur": 5,
           "args": {"launched": False, "chunks": 0, "decode": 0}},
          {"ph": "X", "name": "engine.dispatch", "ts": 800_000_000, "dur": 5,
           "args": {"launched": True, "chunks": 0, "decode": 8}},
          {"ph": "X", "name": "engine.device_inflight", "ts": 800_000_004,
           "dur": 150_000_000, "args": {"rows": 8}}]
    ls = S.launches(sp)
    assert [(l["chunks"], l["decode"], l["ms"]) for l in ls] == [
        (2, 7, 600.0), (0, 8, 150.0)]


def test_attention_rows_from_request_events():
    sp = [{"ph": "b", "name": "req", "ts": 0, "dur": 0,
           "args": {"rid": 1, "prompt_tokens": 100, "replayed": 0}},
          # 60 of the 100 tokens came from the cache: chunks of 30 and 10
          {"ph": "i", "name": "request.prefill_chunk", "ts": 10, "dur": 0,
           "args": {"rid": 1, "tokens": 30, "done": False}},
          {"ph": "i", "name": "request.prefill_chunk", "ts": 20, "dur": 0,
           "args": {"rid": 1, "tokens": 10, "done": True}},
          {"ph": "i", "name": "runner.deliver", "ts": 20, "dur": 0,
           "args": {"rid": 1, "tokens": 1}},
          {"ph": "i", "name": "runner.deliver", "ts": 30, "dur": 0,
           "args": {"rid": 1, "tokens": 2}},
          {"ph": "i", "name": "runner.deliver", "ts": 40, "dur": 0,
           "args": {"rid": 1, "tokens": 3}}]
    assert sorted(S.attention_rows(sp, 0, 100)) == [
        (1, 101), (1, 102), (10, 100), (30, 90)]
    assert sorted(S.attention_rows(sp, 15, 35)) == [(1, 101), (10, 100)]


def test_host_self_time_takes_out_nested_spans():
    sp = [{"ph": "X", "name": "engine.schedule", "ts": 0, "dur": 100,
           "args": {}},
          {"ph": "X", "name": "engine.pack", "ts": 10, "dur": 30, "args": {}}]
    assert S.self_time_ns(sp, ("engine.schedule",)) == 70
    assert S.self_time_ns(sp, ("engine.schedule", "engine.pack")) == 100
