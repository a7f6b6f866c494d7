"""The readers of the program's own spans (``bench/program_spans.py``,
``step_host_share``, ``gc_pause_share``): on a synthetic run with known
spans, when the pairing fails or the ring dropped spans, and on a tiny CPU
run of the program."""
from __future__ import annotations

import pytest

from bench import program_spans as PS
from bench import run
from bench.loop import Recorder
from bench.tests import tiny

T0 = 1000.0  # the program's clock at the window's start


def _run(steps, marks=None, seconds=10.0):
    rec = Recorder()
    rec.steps = [(a, b, None) for a, b in steps]
    rec.marks = dict(marks or {})
    return run.Run(cell={"name": "synthetic"}, hf={}, dims={}, mix={}, seconds=seconds,
                   rec=rec, trace=None, peaks={})


def _program(n=8, sync=0.08, gcs=()):
    """``n`` steps of 0.1 s a second apart from 0.5 s, each with a sync
    child of ``sync`` s, and ``gc`` spans at the given window times."""
    spans, i = [], 1
    for k in range(n):
        a = T0 + k + 0.5
        spans.append((i + 1, i, "serve.step.sync", a + 0.01, a + 0.01 + sync, {}))
        spans.append((i, 0, "serve.step", a, a + 0.1, {"step": k + 1}))
        i += 2
    for a, b in gcs:
        spans.append((i, 0, "gc", T0 + a, T0 + b, {"gen": 2, "collected": 0}))
        i += 1
    return sorted(spans, key=lambda s: s[4])


def _steps(n=8):
    return [(k + 0.5, k + 0.6 + 1e-4) for k in range(n)]


@pytest.fixture
def snapshot(monkeypatch):
    def put(spans, dropped=0):
        snap = {"spans": spans, "counters": {}, "dropped": dropped}
        monkeypatch.setattr(PS, "_snapshot", lambda: snap)

    return put


def _read(name, r):
    return run.load_reader(name)(r)


def test_known_spans(snapshot):
    snapshot(_program(gcs=[(2.5, 2.6), (6.3, 6.35)]))
    r = _run(_steps())
    assert _read("step_host_share", r) == pytest.approx(20.0)
    assert _read("gc_pause_share", r) == pytest.approx(100.0 * 0.15 / 10.0)


def test_traced_stretch_is_left_out(snapshot):
    # the steps at 3.5 s and 4.5 s, the second collection and half the third
    # overlap the traced stretch; the step at 2.5 s has a longer sync
    spans = _program(gcs=[(2.5, 2.6), (4.0, 4.2), (5.35, 5.65)])
    spans = [s if not (s[2] == "serve.step.sync" and abs(s[3] - T0 - 2.51) < 1e-9)
             else (s[0], s[1], s[2], s[3], s[3] + 0.085, s[5]) for s in spans]
    snapshot(spans)
    r = _run(_steps(), marks={"trace_on": 3.55, "trace_off": 5.45})
    host = [0.02] * 5 + [0.015]
    assert _read("step_host_share", r) == pytest.approx(100.0 * sum(host) / 0.6)
    assert _read("gc_pause_share", r) == pytest.approx(100.0 * (0.1 + 0.2) / 8.1)


def test_none_when_a_step_does_not_pair(snapshot):
    spans = _program()
    late = [s for s in spans if s[2] == "serve.step"][3]
    spans = [s if s is not late else (s[0], s[1], s[2], s[3], s[4] + 0.01, s[5]) for s in spans]
    snapshot(spans)
    r = _run(_steps())
    assert _read("step_host_share", r) is None
    assert _read("gc_pause_share", r) is None


def test_none_when_the_program_has_fewer_steps(snapshot):
    snapshot(_program(n=5))
    r = _run(_steps(8))
    assert _read("step_host_share", r) is None and _read("gc_pause_share", r) is None


def test_none_when_the_ring_dropped_spans_of_the_stretch(snapshot):
    spans = _program()
    snapshot(spans[3:], dropped=3)
    r = _run(_steps())
    assert _read("step_host_share", r) is None and _read("gc_pause_share", r) is None


def test_drops_before_the_stretch_do_not_matter(snapshot):
    early = [(90, 0, "serve.admit", T0 - 5.0, T0 - 4.9, {})]  # the oldest held
    snapshot(early + _program(), dropped=100)
    r = _run(_steps())
    assert _read("step_host_share", r) == pytest.approx(20.0)


def test_none_without_the_programs_spans(monkeypatch):
    monkeypatch.setattr(PS, "_snapshot", lambda: None)
    r = _run(_steps())
    assert _read("step_host_share", r) is None and _read("gc_pause_share", r) is None


def test_both_read_a_tiny_cpu_run():
    ex = {}
    hf = tiny.config("qwen3-32b-1chip")
    res = run.run_cell(tiny.cell(hf, tiny.MIX_CLOSED), 2**31 + 21, 1.5, False,
                       require_tpu=False, extras=ex)
    assert res["correct"]
    r = run.Run(cell=tiny.cell(hf, tiny.MIX_CLOSED)["cell"], hf=hf, dims={}, mix={},
                seconds=1.5, rec=ex["rec"], trace=None, peaks={})
    host = _read("step_host_share", r)
    paused = _read("gc_pause_share", r)
    assert host is not None and 0.0 < host <= 100.0
    assert paused is not None and 0.0 <= paused < 100.0
