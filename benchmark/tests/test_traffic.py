"""The stratified length lists and what the seed may and may not change."""
import collections
import json
import os
import statistics

import pytest

from harness import spec
from harness.kinds import closed_loop as CL

MIXES = ("chat", "sysprompt")


@pytest.mark.parametrize("mix", MIXES)
def test_pairs_are_the_stated_distribution(mix):
    t = spec.load_traffic(mix)
    d = t["distribution"]
    p = CL.stratified(d["prompt"]["median"], d["prompt"]["sigma"],
                      d["prompt"]["min"], d["prompt"]["max"])
    o = CL.stratified(d["output"]["median"], d["output"]["sigma"],
                      d["output"]["min"], d["output"]["max"])
    assert len(t["pairs"]) == 64
    assert [a for a, _ in t["pairs"]] == p
    assert [b for _, b in t["pairs"]] == [o[(37 * i + 11) % 64]
                                          for i in range(64)]
    assert sorted(b for _, b in t["pairs"]) == sorted(o)


def test_means_as_stated():
    chat = spec.load_traffic("chat")["pairs"]
    sysp = spec.load_traffic("sysprompt")["pairs"]
    assert statistics.mean(a for a, _ in chat) == pytest.approx(350, abs=5)
    assert statistics.mean(b for _, b in chat) == pytest.approx(152, abs=1)
    assert statistics.mean(a for a, _ in sysp) == pytest.approx(188, abs=3)
    assert min(a for a, _ in chat) >= 32 and max(a for a, _ in chat) <= 2048
    assert min(b for _, b in chat) >= 16 and max(b for _, b in chat) <= 512


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_same_multiset_under_every_seed(mix, seed):
    t = spec.load_traffic(mix)
    want = collections.Counter(map(tuple, t["pairs"]))
    got = collections.Counter()
    for j in range(64):
        r = CL.dealt_request(t, seed, j, vocab=1000)
        plen = len(r["prompt"])
        if r["prefix"] is not None:
            plen -= t["prefixes"][r["prefix"]]["tokens"]
        got[(plen, r["max_tokens"])] += 1
    assert got == want
    # and the list repeats when it runs out
    a = CL.dealt_request(t, seed, 3, 1000)
    b = CL.dealt_request(t, seed, 67, 1000)
    assert (len(a["prompt"]), a["max_tokens"]) == (len(b["prompt"]),
                                                   b["max_tokens"])
    assert a["prompt"] != b["prompt"]           # other token ids


@pytest.mark.parametrize("mix", MIXES)
def test_the_files_order_is_balanced_and_the_same_under_every_seed(mix):
    t = spec.load_traffic(mix)
    order = t["deal"]["order"]
    assert sorted(order) == list(range(64))
    for i in range(0, 64, 16):
        assert sorted(x // 4 for x in order[i:i + 16]) == list(range(16))
    a = [CL.dealt_request(t, 1, j, 100)["max_tokens"] for j in range(64)]
    b = [CL.dealt_request(t, 2, j, 100)["max_tokens"] for j in range(64)]
    assert a == b == [t["pairs"][i][1] for i in order]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_every_block_of_16_holds_each_stratum(seed):
    order = CL.balanced_order(seed, 64, 16)
    assert sorted(order) == list(range(64))
    for i in range(0, 64, 16):
        assert sorted(x // 4 for x in order[i:i + 16]) == list(range(16))
    assert CL.balanced_order(seed, 64, 16) != CL.balanced_order(seed + 1, 64, 16)


def test_tokens_are_a_function_of_the_seed_and_cover_large_seeds():
    a = CL.tokens(2**31 + 5, 0, 3, 50, 32768)
    assert a == CL.tokens(2**31 + 5, 0, 3, 50, 32768)
    assert a != CL.tokens(2**31 + 6, 0, 3, 50, 32768)
    assert all(0 <= x < 32768 for x in a)


def test_primer_phases_spread_evenly():
    ph = CL.primer_phases(32, 152)
    assert ph == CL.primer_phases(32, 152) and ph != sorted(ph)
    assert min(ph) == 1 and max(ph) == 152 and len(set(ph)) == 32
    t = spec.load_traffic("sysprompt")
    a, b = (CL.primer_request(t, s, 5, 1000) for s in (1, 2))
    assert a["max_tokens"] == b["max_tokens"] == ph[5]      # not from the seed
    assert a["prompt"] != b["prompt"] and a["prefix"] == 1
    assert len(a["prompt"]) == 1589 + 32


def test_sysprompt_prefixes_dealt_in_turn_and_shared():
    t = spec.load_traffic("sysprompt")
    r0 = CL.dealt_request(t, 5, 0, 32768)
    r1 = CL.dealt_request(t, 5, 1, 32768)
    r2 = CL.dealt_request(t, 5, 2, 32768)
    assert (r0["prefix"], r1["prefix"], r2["prefix"]) == (0, 1, 0)
    assert r0["prompt"][:1411] == r2["prompt"][:1411]
    assert r0["prompt"][1411:] != r2["prompt"][1411:]
    assert len(r1["prompt"]) - 1589 in [a for a, _ in t["pairs"]]
    assert 1411 % 16 and 1589 % 16              # not page multiples
    hit = (1411 + 1589) / 2
    assert hit / (hit + 188) == pytest.approx(0.89, abs=0.01)


def test_rebuild_prompt_gives_what_was_sent():
    t = spec.load_traffic("sysprompt")
    for req in (CL.dealt_request(t, 4, 9, 500),
                CL.primer_request(t, 4, 3, 500)):
        assert CL.rebuild_prompt(t, 4, req, 500) == req["prompt"]
    flat = [it for st in t["warmup"] for it in st["requests"]]
    req = CL.warm_request(t, 4, 5, flat[5], 500)
    assert CL.rebuild_prompt(t, 4, req, 500) == req["prompt"]


def test_longest_request_fits_the_context():
    for cell in spec.load_benchmark()["workloads"]:
        cfg = spec.load_config(spec.load_benchmark(), cell["config"])
        t = spec.load_traffic(cell["traffic"])
        longest = max(a + b for a, b in t["pairs"]) + max(
            [p["tokens"] for p in t["prefixes"]] or [0])
        assert longest <= cfg["serving"]["max_model_len"]
        assert longest <= t["reference_pad_to"]
        assert max(b for _, b in t["pairs"]) <= t["reference_score_rows"]
