"""The benchmark's arithmetic on hand-made lists."""
import math
import statistics

import pytest

from harness import client, costs, peaks, spec, stats


def test_percentile_hand_made():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 0) == 10
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 100) == 50
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile([7], 99) == 7
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_percentile_matches_numpy():
    import numpy as np
    rng = np.random.default_rng(0)
    xs = rng.lognormal(size=501).tolist()
    for q in (5, 50, 95, 99):
        assert stats.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))


def test_top_share_mean():
    xs = list(range(1, 101))                    # 1..100
    assert stats.top_share_mean(xs, 0.05) == pytest.approx(98.0)
    assert stats.top_share_mean([3.0], 0.05) == 3.0
    assert stats.top_share_mean([1, 2, 3], 0.05) == 3.0   # at least one


def test_rate_over_window_edges():
    # a request that straddles an edge counts the tokens that arrived inside
    stamps = [5, 9, 10, 15, 19, 20, 25]
    assert stats.rate_in_window(stamps, 10, 20) == pytest.approx(3 / 10)
    with pytest.raises(ValueError):
        stats.rate_in_window(stamps, 20, 20)


def test_gaps_count_by_their_later_token():
    st = [0, 8, 12, 19, 23]
    assert stats.gaps_in_window(st, 10, 20) == [4, 7]


def test_histogram_buckets():
    assert stats.histogram([1, 50, 99, 100, 5000], (50, 100)) == [1, 2, 2]


def test_spread_is_the_contracts():
    xs = [100, 101, 102, 103, 104, 110]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / 102.5)


def _rec(kind, t_send, stamps, max_tokens=None, finish="length"):
    return {"kind": kind, "index": 0, "prefix": None, "prompt_tokens": 4,
            "max_tokens": max_tokens or len(stamps), "t_send": t_send,
            "stamps": stamps, "tokens": [1] * len(stamps), "finish": finish,
            "error": None, "cut": False}


def test_window_view_on_hand_made_records():
    s = 1_000_000_000                           # ns
    recs = [
        _rec("warm", 0, [1 * s, 2 * s]),                    # never counted
        _rec("primer", 9 * s, [int(9.5 * s), 10 * s, 11 * s]),
        _rec("deal", 10 * s, [12 * s, 13 * s, 15 * s]),
        _rec("deal", 18 * s, [19 * s, 21 * s]),             # straddles
    ]
    v = client.window_view(recs, 10 * s, 20 * s)
    # tokens inside [10, 20): 10, 11, 12, 13, 15, 19
    assert v["tokens"] == 6
    assert v["out_tokens_per_s"] == pytest.approx(0.6)
    # ttft: dealt requests whose first token came inside; primers left out
    assert sorted(v["ttfts_ms"]) == [1000.0, 2000.0]
    # gaps by later token inside: 0.5 (9.5->10), 1 (10->11), 1, 2; 19->21 is out
    assert sorted(v["gaps_ms"]) == [500.0, 1000.0, 1000.0, 2000.0]
    e = client.end_to_end(v)
    assert e["gap_p95_ms"] == pytest.approx(stats.percentile(v["gaps_ms"], 95))
    assert e["gap_top5_mean_ms"] == 2000.0
    th = client.thirds(recs, 10 * s, 19 * s)
    assert th == pytest.approx([1.0, 2 / 3, 0.0])


def test_failures_counted_against_attempts():
    ok = _rec("deal", 0, [1, 2, 3])
    short = _rec("deal", 0, [1, 2], max_tokens=3)
    other = _rec("deal", 0, [1], finish="aborted")
    err = dict(_rec("deal", 0, [], finish=None), error="http 500")
    cut = dict(_rec("deal", 0, [1], finish=None, max_tokens=9), cut=True)
    lost = _rec("deal", 0, [1], finish=None, max_tokens=9)
    assert client.failures([ok, short, other, err, cut, lost]) == (6, 4, 1)


def test_attention_counts_hand_worked():
    # one decode row at K/V length 100, 32 heads of 128, 8 K/V heads, bf16
    arch = spec.load_shapes("llama_dense")

    def cfg(heads, kv_heads, head_dim, layers=1):
        return {"hidden_size": heads * head_dim, "num_attention_heads": heads,
                "num_key_value_heads": kv_heads, "intermediate_size": 1,
                "vocab_size": 1, "num_hidden_layers": layers}

    ops, byt = arch.attention_row(cfg(32, 8, 128), 1, 100)
    assert ops == 4 * 32 * 128 * 100
    assert byt == (2 * 100 * 8 * 128 + 2 * 1 * 8 * 128 + 2 * 1 * 32 * 128) * 2
    # a 4-token chunk that ends at length 10 sees 7 + 8 + 9 + 10 keys
    ops, _ = arch.attention_row(cfg(2, 1, 8), 4, 10)
    assert ops == 4 * 2 * 8 * 34
    # a whole prompt of n tokens: n (n + 1) / 2 pairs
    ops, _ = arch.attention_row(cfg(1, 1, 1), 16, 16)
    assert ops == 4 * 136
    # every layer does the same, and rows add up
    tot = costs.attention_total(arch, cfg(32, 8, 128, layers=3),
                                [(1, 100), (1, 100)])
    one = arch.attention_row(cfg(32, 8, 128), 1, 100)
    assert tot == (one[0] * 6, one[1] * 6)


def test_least_seconds_says_which_bound():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    t, bound = costs.least_seconds(197e12, 1.0, pk)
    assert (t, bound) == (1.0, "compute")
    t, bound = costs.least_seconds(1.0, 819e9 * 2, pk)
    assert (t, bound) == (2.0, "memory")
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
