"""CPU checks of the benchmark's own arithmetic: the traffic generator, the
metric arithmetic, the trace reduction and the kernels' work counts."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench import stats, trace, traffic
from bench.kernels import flash_attention, flash_decode, grouped_gemm, model
from bench.tests import tiny

MIXES = Path(__file__).resolve().parents[1] / "traffic"


@pytest.mark.parametrize("name", sorted(p.stem for p in MIXES.glob("*.json")))
def test_traffic_same_seed_same_requests(name):
    mix = json.loads((MIXES / f"{name}.json").read_text())
    a = traffic.generate(mix, 2**31 + 7, 1000, seconds=30, slots=16)
    b = traffic.generate(mix, 2**31 + 7, 1000, seconds=30, slots=16)
    assert [(r.rid, r.output, r.due) for r in a.requests] == \
           [(r.rid, r.output, r.due) for r in b.requests]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a.requests, b.requests))


@pytest.mark.parametrize("name", sorted(p.stem for p in MIXES.glob("*.json")))
def test_traffic_lengths_in_buckets(name):
    mix = json.loads((MIXES / f"{name}.json").read_text())
    s = traffic.generate(mix, 5, 1000, seconds=30, slots=16)
    buckets = set(mix["prompt"]["buckets"])
    assert s.requests and all(len(r.prompt) in buckets for r in s.requests)
    o = mix["output"]
    rest = s.requests[s.concurrency:]
    assert all(o["min"] <= r.output <= o["max"] for r in rest)
    assert all(2 <= r.output <= o["max"] for r in s.requests)
    assert all(0 <= t < 1000 for r in s.requests for t in r.prompt)
    assert s.max_len >= max(len(r.prompt) + r.output for r in s.requests)
    if not s.closed:
        due = [r.due for r in s.requests]
        assert due == sorted(due) and due[-1] < 30


def test_traffic_seeds_share_the_work():
    """Two seeds serve the same multiset of lengths, in another order."""
    mix = tiny.MIX_CLOSED
    a = traffic.generate(mix, 1, 256, seconds=5, slots=4)
    b = traffic.generate(mix, 2, 256, seconds=5, slots=4)
    la = [len(r.prompt) for r in a.requests]
    lb = [len(r.prompt) for r in b.requests]
    assert sorted(la) == sorted(lb) and la != lb
    flat = {k: v for k, v in mix.items() if k != "first_cohort"}
    oa = [r.output for r in traffic.generate(flat, 1, 256, seconds=5, slots=4).requests]
    ob = [r.output for r in traffic.generate(flat, 2, 256, seconds=5, slots=4).requests]
    assert sorted(oa) == sorted(ob) and oa != ob


def test_open_loop_is_poisson_with_independent_lengths():
    """Open-loop gaps are exponential at the mix's rate and lengths are drawn
    one by one, so seeds differ in how much work and how many bursts a
    window holds."""
    mix = dict(tiny.MIX_OPEN, arrival={"process": "poisson", "rate_per_s": 50.0,
                                       "ramp_s": 0.0})
    a = traffic.generate(mix, 2**31 + 13, 256, seconds=200, slots=4)
    b = traffic.generate(mix, 2**31 + 14, 256, seconds=200, slots=4)
    gaps = np.diff([r.due for r in a.requests])
    assert abs(len(a.requests) / 200 - 50.0) < 3.0
    assert abs(gaps.mean() - 0.02) < 0.002
    assert abs(np.median(gaps) - 0.02 * np.log(2)) < 0.002   # exponential
    assert 0.0 <= a.requests[0].due < 30 and a.requests[-1].due < 200
    la = sorted(len(r.prompt) for r in a.requests)
    lb = sorted(len(r.prompt) for r in b.requests)
    assert la != lb


def test_every_round_holds_one_value_of_each_band():
    rng = np.random.default_rng(3)
    vals = np.arange(64)
    out = traffic.stratified_order(vals, rng)
    assert sorted(out) == list(vals) and list(out) != list(vals)
    for r in range(8):
        assert sorted(out[8 * r: 8 * r + 8] // 8) == list(range(8))


def test_p95_is_over_all_requests():
    due = {r: 0.0 for r in range(100)}
    first = {r: 0.001 * (r + 1) for r in range(100)}
    assert stats.percentile(stats.ttfts(due, first, 1.0), 95) == pytest.approx(0.09505)
    # a request due before the window does not count
    due[100], first[100] = -1.0, 5.0
    assert stats.percentile(stats.ttfts(due, first, 1.0), 95) == pytest.approx(0.09505)


def test_rate_is_over_the_whole_window_and_a_stall_moves_both():
    steady = {0: [0.1 * i for i in range(100)]}          # 10 tokens/s for 10 s
    assert stats.tokens_in(steady, 10.0) / 10.0 == pytest.approx(10.0)
    assert stats.percentile(stats.gaps(steady, 10.0), 95) == pytest.approx(0.1)
    stalled = {0: [0.1 * i for i in range(50)] + [7.0 + 0.1 * i for i in range(30)]}
    rate = stats.tokens_in(stalled, 10.0) / 10.0
    p95 = stats.percentile(stats.gaps(stalled, 10.0), 95)
    assert rate == pytest.approx(8.0) and rate < 10.0
    assert p95 > 0.1
    # tokens past the window's end count for neither
    assert stats.tokens_in({0: [9.9, 10.0, 10.5]}, 10.0) == 1
    assert stats.gaps({0: [9.9, 10.0]}, 10.0) == []


# device operations (a loop holding two of them) and the harness's host spans
OPS = [("while.3", 0.0, 2.0), ("fusion.1", 0.0, 1.0), ("flash_decode.7", 1.0, 2.0),
       ("fusion.2", 3.0, 4.0), ("copy.3", 9.0, 10.0)]
SPANS = [("step", 0.0, 2.2), ("admit", 2.2, 4.5), ("wait", 4.5, 6.0)]
RECORDED = Path(__file__).resolve().parent / "data" / "trace_moe_decode.json"


def test_trace_reduction():
    r = trace.reduce(OPS, SPANS)
    assert r["window_s"] == pytest.approx(6.0)
    assert r["busy_s"] == pytest.approx(3.0)          # [0, 2] and [3, 4]
    assert r["op_s"] == pytest.approx({"while": 0.0, "fusion": 2.0, "flash_decode": 1.0})
    assert r["op_n"] == {"while": 1, "fusion": 2, "flash_decode": 1}
    assert trace.kernel_seconds(r, flash_decode.NAMES) == pytest.approx(1.0)
    assert [n for n, _ in r["idle_gaps"]] == ["wait", "admit"]
    assert [s for _, s in r["idle_gaps"]] == pytest.approx([2.0, 1.0])
    assert 1.0 - r["busy_s"] / r["window_s"] == pytest.approx(0.5)


def test_trace_reduction_without_spans_is_empty():
    assert trace.reduce(OPS, []) == {}


def test_trace_reduction_on_a_recorded_chip_trace():
    rec = json.loads(RECORDED.read_text())
    ops = [tuple(o) for o in rec["ops"]]
    spans = [tuple(s) for s in rec["spans"]]
    r = trace.reduce(ops, spans)
    w0, w1 = spans[0][1], spans[-1][2]
    assert r["window_s"] == pytest.approx(w1 - w0)
    # busy: the union, at least the longest operation, at most their sum
    longest = max(min(b, w1) - max(a, w0) for _, a, b in ops)
    assert longest <= r["busy_s"] <= r["window_s"]
    assert r["busy_s"] < sum(min(b, w1) - max(a, w0) for _, a, b in ops if b > w0 and a < w1)
    # kernels are leaves: their self time is the sum of their durations
    fd = sum(b - a for n, a, b in ops if n.startswith("flash_decode"))
    gg = sum(b - a for n, a, b in ops if n.startswith("grouped_gemm"))
    assert fd > 0 and gg > 0
    assert trace.kernel_seconds(r, flash_decode.NAMES) == pytest.approx(fd)
    assert trace.kernel_seconds(r, grouped_gemm.NAMES) == pytest.approx(gg)
    # a loop's self time excludes the operations inside it
    loops = [(a, b) for n, a, b in ops if n.startswith("while")]
    inner = sum(b - a for n, a, b in ops if not n.startswith("while")
                and any(la <= a and b <= lb for la, lb in loops))
    assert r["op_s"]["while"] == pytest.approx(sum(b - a for a, b in loops) - inner)
    assert sum(s for _, s in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-9
    assert {n for n, _ in r["idle_gaps"]} <= {"step", "admit", "none"}


def test_flash_decode_work_by_hand():
    # 2 tokens attending to 3 and 5 rows; 4 query heads, 2 KV heads of 8
    flops, bytes_ = flash_decode.work([3, 5], nq=4, nkv=2, hd=8)
    assert flops == 4 * 4 * 8 * 8                      # 1024
    assert bytes_ == 8 * 2 * 2 * 8 * 2 + 2 * 2 * 4 * 8 * 2   # 512 KV + 256 q/o


def test_grouped_gemm_work_by_hand():
    assert grouped_gemm.work(6, 4, 8, 16) == (1536.0, 4 * 8 * 16 * 2 + 6 * 24 * 2)
    assert grouped_gemm.work(2, 4, 8, 16) == (512.0, 2 * 8 * 16 * 2 + 2 * 24 * 2)
    f, b = grouped_gemm.swiglu(3, 2, 4, 8, 16)          # 6 routed rows
    assert f == 3 * 2 * 6 * 8 * 16
    assert b == 3 * 4 * 8 * 16 * 2 + 3 * 6 * 24 * 2
    # 4 tokens, top-2, 2 held of 8: 8 assignments, 2 of them to this chip
    f, b = grouped_gemm.swiglu(4, 2, 8, 8, 16, held=2, router=8)
    assert f == 3 * 2 * 2 * 8 * 16
    assert b == 3 * 2 * 8 * 16 * 2 + 3 * 2 * 24 * 2
    assert grouped_gemm.swiglu(4, 2, 8, 8, 16) == grouped_gemm.swiglu(4, 2, 8, 8, 16, held=8)


def test_flash_attention_work_by_hand():
    flops, bytes_ = flash_attention.work(4, nq=2, nkv=1, hd=8)
    assert flops == 4 * 2 * 8 * 10                      # 10 causal pairs
    assert bytes_ == 4 * (2 * 2 + 2 * 1) * 8 * 2


def test_model_flops_by_hand():
    dm = {"d": 4, "nq": 2, "nkv": 1, "hd": 2, "ff": 3, "experts": 0, "top_k": 0,
          "layers": 1, "vocab": 5}
    # per layer and token: projections 64 + 32, FFN 72; attention 16 per row
    assert model.decode(dm, [1, 2]) == 2 * 168 + 16 * 3 + 2 * 2 * 4 * 5
    assert model.prefill(dm, 2) == 2 * 168 + 16 * 3 + 2 * 4 * 5
    moe = dict(dm, experts=4, router=4, top_k=2)
    assert model.decode(moe, [1]) == 96 + 2 * 4 * 4 + 6 * 4 * 3 * 2 + 16 + 40
    # a share of 1 of 4 experts: the router at its width, a quarter of the routed FFN
    share = dict(moe, experts=1)
    assert model.decode(share, [1]) == 96 + 2 * 4 * 4 + 6 * 4 * 3 * 2 / 4 + 16 + 40
