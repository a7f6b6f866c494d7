"""The reductions of ``tools/span_report.py``: step splits, slow steps,
idle gaps named by program spans and device time by named scope, on
synthetic lists and on a recorded piece of a chip profile."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
RECORDED = ROOT / "tests" / "data" / "span_trace.json"


@pytest.fixture(scope="module")
def SR():
    spec = importlib.util.spec_from_file_location("span_report", ROOT / "tools" / "span_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _steps(durations, sync_share=0.9):
    """serve.step spans of the given ms, each with a sync child covering
    ``sync_share`` of it and a prepare child before it."""
    spans, i, t = [], 1, 0.0
    for ms in durations:
        d = ms * 1e-3
        spans.append((i + 1, i, "serve.step.prepare", t, t + 0.05 * d, {}))
        spans.append((i + 2, i, "serve.step.sync", t + 0.05 * d, t + (0.05 + sync_share) * d, {}))
        spans.append((i, 0, "serve.step", t, t + d, {"step": i}))
        i, t = i + 3, t + d + 0.001
    return spans


def test_median_split_and_slow_steps(SR):
    spans = _steps([10.0, 10.0, 10.0, 12.0, 40.0])
    gc_in_slow = (99, 13, "gc", 0.06, 0.065, {"gen": 2, "collected": 7})
    steps = SR.step_splits(spans + [gc_in_slow])
    med = SR.median_split(steps)
    assert med["serve.step"] == pytest.approx(10.0)
    assert med["serve.step.sync"] == pytest.approx(9.0)
    assert med["serve.step.prepare"] == pytest.approx(0.5)
    assert med["other"] == pytest.approx(0.5)
    slow = SR.slow_steps(steps)
    assert [s["ms"] for s in slow] == [pytest.approx(40.0)]
    assert slow[0]["inside"] == [("gc", pytest.approx(5.0), {"gen": 2, "collected": 7})]
    only = {s[0] for s in spans if s[2] == "serve.step"}
    assert len(SR.step_splits(spans, only={min(only)})) == 1


def test_idle_gaps_are_named_by_the_innermost_span(SR):
    spans = [("serve.step", 0.0, 1.0), ("serve.step.sync", 0.5, 1.0),
             ("serve.step", 1.0, 2.0), ("serve.step.verify", 1.1, 1.4), ("gc", 1.2, 1.3)]
    ops = [("m:a.1", 0.0, 0.5), ("m:b.2", 0.55, 1.1), ("m:c.3", 1.4, 2.0)]
    gaps = SR.named_gaps(ops, spans)
    assert gaps == [("gc", pytest.approx(0.3)), ("serve.step.sync", pytest.approx(0.05))]
    assert SR.innermost(spans, 5.0) == "none"


def test_scope_of_takes_the_innermost_scope(SR):
    assert SR.scope_of("jit(spec_step)/layers/while/body/closed_call/attn.kernel/"
                       "attn.kv_read/transpose") == "attn.kv_read"
    assert SR.scope_of("jit(spec_step)/layers/while/dynamic_slice") == "layers"
    assert SR.scope_of("jit(spec_step)/head/dot_general") == "head"
    assert SR.scope_of("jit(spec_step)/while/body/add") == "(none)"
    assert SR.scope_of("") == "(none)"


def test_scope_seconds_sum_self_times(SR):
    # a loop (in "layers") holds two body operations on the same line
    ops = [("m:while.1", 0.0, 1.0), ("m:fusion.2", 0.1, 0.4), ("m:copy.3", 0.5, 0.6),
           ("m:fusion.4", 1.0, 1.2)]
    names = {"m:while.1": "jit(f)/layers/while", "m:fusion.2": "jit(f)/layers/while/body/ffn/dot",
             "m:copy.3": "jit(f)/layers/while/body/attn.kernel/attn.kv_read/transpose"}
    got = SR.scope_seconds(ops, names)
    assert got == {"layers": pytest.approx(0.6), "ffn": pytest.approx(0.3),
                   "(none)": pytest.approx(0.2), "attn.kv_read": pytest.approx(0.1)}


def test_op_names_from_optimised_hlo(SR):
    text = ('HloModule jit_f, is_scheduled=true\n\n'
            '  %copy.3 = bf16[2]{0} copy(%p), metadata={op_name="jit(f)/layers/while" '
            'source_file="x.py"}\n'
            '  ROOT %fusion.4 = f32[2]{0} fusion(%a), kind=kLoop, '
            'metadata={op_name="jit(f)/head/mul"}\n'
            '  %param.0 = f32[2]{0} parameter(0)\n')
    assert SR.hlo_op_names(text) == {"jit_f:copy.3": "jit(f)/layers/while",
                                     "jit_f:fusion.4": "jit(f)/head/mul"}
    assert SR.module_name("jit_spec_step(12)") == SR.module_name("jit_spec_step.3") == "jit_spec_step"


def test_fixture_keeps_two_steps_from_the_start(SR):
    tr = {"ops": [("m:a.1", 10.0, 10.4), ("m:b.2", 11.0, 11.5), ("m:c.3", 13.0, 13.1)],
          "op_names": {"m:a.1": "jit(f)/ffn/x", "m:c.3": "jit(f)/head/y"},
          "spans": [("serve.step", 10.0, 10.9), ("serve.step", 11.0, 11.9),
                    ("serve.step", 13.0, 13.9)]}
    fx = SR.fixture(tr, 9.5)
    assert fx["ops"] == [("m:a.1", 0.0, pytest.approx(0.4)), ("m:b.2", 1.0, 1.5)]
    assert fx["op_names"] == {"m:a.1": "jit(f)/ffn/x"}
    assert len(fx["spans"]) == 2
    assert SR.module_seconds(fx["ops"]) == {"m": pytest.approx(0.9)}


def test_recorded_trace_names_gaps_and_scopes(SR):
    """Two decode steps of ``qwen3-32b-1chip.decode`` on one TPU v5e, the
    operations named from the decode step's optimised HLO: the steps' idle
    gaps fall inside ``serve.step.sync`` by the trace's clocks, and the
    device's self time splits over the decode launch's named scopes."""
    rec = json.loads(RECORDED.read_text())
    ops = [tuple(o) for o in rec["ops"]]
    spans = [tuple(s) for s in rec["spans"]]
    assert [n for n, _, _ in spans].count("serve.step") == 2
    gaps = SR.named_gaps(ops, spans)
    assert [n for n, _ in gaps[:2]] == ["serve.step.sync"] * 2
    assert all(s > 1e-3 for _, s in gaps[:2])
    scopes = SR.scope_seconds(ops, rec["op_names"])
    total = sum(s for _, s in SR.self_times(ops))
    assert sum(scopes.values()) == pytest.approx(total)
    for scope in ("layers", "attn.kv_read", "attn.kernel", "ffn", "attn.qkv"):
        assert scopes[scope] > 0.0, scope
    kernel = [k for k, _, _ in ops if "flash_decode" in k]
    assert kernel and {SR.scope_of(rec["op_names"][k]) for k in kernel} == {"attn.kernel"}
    mods = SR.module_seconds(ops)
    assert next(iter(mods)) == "jit_spec_step"
