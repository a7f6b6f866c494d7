"""Where a served step's time goes, read from the program's own spans
(``repro.obs``) and, with ``--trace 1``, from a profile of the window.

    python tools/span_report.py --workload qwen3-32b-1chip.decode \
        --seed 3000000201 --seconds 51 --trace 1 --out report.json

It runs one cell of ``BENCHMARK.json`` through ``bench.run.run_cell`` (on
the chip; ``--cpu`` runs a tiny cell on the CPU for a rehearsal) and prints:

* the median ``serve.step`` split into its child spans, in ms;
* every step over 1.5 times the median, with its split and the ``gc`` and
  ``compile`` spans inside it;
* from the profile, the ten longest device idle gaps, each named by the
  innermost program span covering its midpoint (the trace's device stamps
  run about 0.9 ms early against the host's, so a gap of a few ms may
  belong to the span after the one named);
* device self time by program and by named scope (``jax.named_scope`` in
  the decode launch); the device events carry no operation name, so the
  names come from the decode step's optimised HLO, compiled by the tool;
* with ``--span-cost``, what one span costs with no profile and during one.

The reductions are pure functions over lists, so they are checked on a
small recorded trace (``tests/data/span_trace.json``, from a v5e).
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import program_spans as PS  # noqa: E402
from bench.trace import _union, base_name, self_times, short_name  # noqa: E402

# the named scopes of the decode launch and the admission prefill
SCOPES = ("embed", "layers", "attn.qkv", "attn.kv_read", "attn.kernel", "attn.kv_write",
          "attn.out", "ffn", "head")
STEP = "serve.step"
SLOW = 1.5

Span = Tuple[int, int, str, float, float, Dict]   # id, parent, name, start, end, attrs
Event = Tuple[str, float, float]


# ---------------------------------------------------------------------------
# reductions (pure)
# ---------------------------------------------------------------------------

def step_splits(spans: Sequence[Span], only: Optional[set] = None) -> List[Dict]:
    """Each ``serve.step`` (of ids ``only``, if given) with its duration,
    its children's durations by name (ms) and the ``gc`` / ``compile``
    spans that lie inside it."""
    kids: Dict[int, List[Span]] = {}
    for s in spans:
        kids.setdefault(s[1], []).append(s)
    out = []
    for s in spans:
        if s[2] != STEP or (only is not None and s[0] not in only):
            continue
        split: Dict[str, float] = {}
        for c in kids.get(s[0], []):
            split[c[2]] = split.get(c[2], 0.0) + (c[4] - c[3]) * 1e3
        inside = [(x[2], (x[4] - x[3]) * 1e3, x[5]) for x in spans
                  if x[2] in ("gc", "compile") and x[3] >= s[3] and x[4] <= s[4]]
        out.append({"id": s[0], "start": s[3], "ms": (s[4] - s[3]) * 1e3,
                    "split": split, "inside": inside, "attrs": s[5]})
    return out


def median_split(steps: Sequence[Dict]) -> Dict[str, float]:
    """Per child, the median of its ms over the steps; ``serve.step`` is the
    median step and ``other`` what no child covers in it."""
    if not steps:
        return {}
    names = sorted({n for s in steps for n in s["split"]})
    med = {n: statistics.median(s["split"].get(n, 0.0) for s in steps) for n in names}
    med[STEP] = statistics.median(s["ms"] for s in steps)
    med["other"] = statistics.median(s["ms"] - sum(s["split"].values()) for s in steps)
    return med


def slow_steps(steps: Sequence[Dict], factor: float = SLOW) -> List[Dict]:
    if not steps:
        return []
    med = statistics.median(s["ms"] for s in steps)
    return [s for s in steps if s["ms"] > factor * med]


def innermost(spans: Sequence[Event], t: float) -> str:
    """The shortest span covering ``t`` (``"none"`` if none does)."""
    cover = [(b - a, n) for n, a, b in spans if a <= t <= b]
    return min(cover)[1] if cover else "none"


def named_gaps(ops: Sequence[Event], spans: Sequence[Event], top: int = 10) -> List[Tuple[str, float]]:
    """The longest stretches with no device operation between the first and
    the last program span, each named by the innermost span at its midpoint."""
    if not spans:
        return []
    w0, w1 = min(a for _, a, _ in spans), max(b for _, _, b in spans)
    busy = _union((max(a, w0), min(b, w1)) for _, a, b in ops if b > w0 and a < w1)
    gaps, t = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    named = [(innermost(spans, 0.5 * (a + b)), b - a) for a, b in gaps]
    return sorted(named, key=lambda x: -x[1])[:top]


def scope_of(op_name: str) -> str:
    """The innermost named scope in an operation's name path, ``"(none)"``
    if it lies in none."""
    for part in reversed(op_name.split("/")[:-1]):
        if part in SCOPES:
            return part
    return "(none)"


def module_seconds(ops: Sequence[Event]) -> Dict[str, float]:
    """Device self seconds by program (the ``<module>`` of each key)."""
    out: Dict[str, float] = {}
    for key, s in self_times(ops):
        mod = key.split(":", 1)[0]
        out[mod] = out.get(mod, 0.0) + s
    return dict(sorted(out.items(), key=lambda x: -x[1]))


def scope_seconds(ops: Sequence[Event], op_names: Dict[str, str]) -> Dict[str, float]:
    """Device self seconds summed by named scope; ``ops`` are named by the
    keys of ``op_names``."""
    out: Dict[str, float] = {}
    for key, s in self_times(ops):
        scope = scope_of(op_names.get(key, ""))
        out[scope] = out.get(scope, 0.0) + s
    return dict(sorted(out.items(), key=lambda x: -x[1]))


# ---------------------------------------------------------------------------
# the profile
# ---------------------------------------------------------------------------

def load_trace(trace_dir: str) -> Dict:
    """Device operations, keyed ``<module>:<hlo name>`` (the module is the
    "XLA Modules" event the operation starts in), and the program's host
    spans (``serve.*``, ``gc``) of the newest ``.xplane.pb``.  The device
    events carry no operation name; :func:`hlo_op_names` supplies them."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    ops: List[Event] = []
    spans: List[Event] = []
    if not paths:
        return {"ops": ops, "spans": spans, "op_names": {}}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, module_name(e.name))
                          for e in (lines["XLA Modules"].events if "XLA Modules" in lines else []))
            starts = [m[0] for m in mods]
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else []):
                i = bisect.bisect_right(starts, e.start_ns) - 1
                mod = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else ""
                ops.append((f"{mod}:{short_name(e.name)}", e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve.") or e.name == "gc":
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return {"ops": ops, "spans": spans, "op_names": {}}


def module_name(name: str) -> str:
    """``jit_spec_step(12)`` or ``jit_spec_step.3`` -> ``jit_spec_step``."""
    return base_name(name.split("(")[0].strip())


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """``<module>:<instruction>`` -> its ``op_name``, from an optimised HLO
    text (the module named by its ``HloModule`` line)."""
    head = re.match(r"HloModule ([\w.\-]+)", hlo_text)
    module = module_name(head.group(1)) if head else ""
    out = {}
    pat = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"')
    for line in hlo_text.splitlines():
        m = pat.match(line)
        if m:
            out[f"{module}:{m.group(1)}"] = m.group(2)
    return out


def fixture(tr: Dict, around: float, steps: int = 2) -> Dict:
    """A small piece of a profile for tests: the operations and spans of
    ``steps`` ``serve.step`` spans from the one starting at or after
    ``around``, times from the piece's start."""
    st = sorted((a, b) for n, a, b in tr["spans"] if n == STEP and a >= around)[:steps]
    if not st:
        return {}
    lo, hi = st[0][0], st[-1][1]
    ops = [(k, a - lo, b - lo) for k, a, b in tr["ops"] if b > lo and a < hi]
    keys = {k for k, _, _ in ops}
    return {"ops": ops, "op_names": {k: v for k, v in tr["op_names"].items() if k in keys},
            "spans": [(n, a - lo, b - lo) for n, a, b in tr["spans"] if b > lo and a < hi]}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def span_cost(n: int = 100_000) -> Dict[str, float]:
    """Microseconds one span costs (open and close, two attributes, no
    nesting) with no profile running and during a profile."""
    import jax

    from repro import obs

    ring = obs.Ring(1024)

    def per_span():
        t = time.perf_counter()
        for i in range(n):
            with obs.span("cost", ring=ring, step=i, active=32):
                pass
        return (time.perf_counter() - t) / n * 1e6

    out = {"no_profile_us": per_span()}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out["profiling_us"] = per_span()
        finally:
            jax.profiler.stop_trace()
    return out


def report(spans: Sequence[Span], trace: Optional[Dict], only: Optional[set] = None) -> Dict:
    steps = step_splits(spans, only)
    out = {"steps": len(steps), "median_ms": median_split(steps),
           "slow": [{k: s[k] for k in ("ms", "split", "inside", "attrs")}
                    for s in slow_steps(steps)]}
    if trace and trace["ops"]:
        out["idle_gaps"] = named_gaps(trace["ops"], trace["spans"])
        out["scope_s"] = scope_seconds(trace["ops"], trace["op_names"])
        out["module_s"] = module_seconds(trace["ops"])
    return out


def _print(rep: Dict) -> None:
    print(f"{rep['steps']} serve.step spans; median split (ms):")
    for k, v in rep["median_ms"].items():
        print(f"  {k:24s} {v:10.3f}")
    print(f"steps over {SLOW} x the median: {len(rep['slow'])}")
    for s in rep["slow"]:
        parts = ", ".join(f"{k} {v:.1f}" for k, v in s["split"].items())
        print(f"  step {s['attrs'].get('step')}: {s['ms']:.1f} ms ({parts}); inside: {s['inside']}")
    if "idle_gaps" in rep:
        print("longest device idle gaps (innermost program span, ms):")
        for n, s in rep["idle_gaps"]:
            print(f"  {n:24s} {s * 1e3:10.3f}")
        for title, key in (("module", "module_s"), ("named scope", "scope_s")):
            print(f"device self time by {title} (s):")
            for k, v in rep[key].items():
                print(f"  {k:24s} {v:10.4f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default="", help="write the report as JSON here")
    ap.add_argument("--fixture", default="", help="write a two-step piece of the profile here")
    ap.add_argument("--span-cost", action="store_true")
    ap.add_argument("--cpu", action="store_true",
                    help="a tiny cell of the configuration on the CPU (rehearsal)")
    args = ap.parse_args(argv)

    from bench import run as R

    R.use_compile_cache()
    from repro import obs

    cell = R.load_cell(args.workload)
    if args.cpu:
        from bench.tests import tiny

        cell = tiny.cell(tiny.config(cell["cell"]["config"]), tiny.MIX_CLOSED)
    keep = tempfile.mkdtemp(prefix="span_report_") if args.trace else ""
    hlo: Dict[str, str] = {}

    def grab_hlo(rep):
        # the device events carry no operation name: take them from the
        # decode step's optimised HLO, compiled once here (the contiguous
        # step's three control words; a paged step's scopes stay unnamed)
        if rep.paged:
            return
        import jax
        import numpy as np

        sds = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        B, T = rep.B, rep.T
        ctl = [np.zeros((B, T), np.int32), np.zeros((B,), np.int32), np.zeros((B,), np.int32)]
        with rep.mesh:
            c = rep._decode.lower(jax.tree.map(sds, rep.params), jax.tree.map(sds, rep.cache),
                                  *ctl).compile()
        hlo.update(hlo_op_names(c.as_text()))

    ex: Dict = {}
    res = R.run_cell(cell, args.seed, args.seconds, bool(args.trace), keep_trace=keep,
                     require_tpu=not args.cpu, on_replica=grab_hlo if args.trace else None,
                     extras=ex)
    if res is None:
        return 1
    snap = obs.snapshot()
    # the window's steps, clear of the traced stretch, on the window's clock
    run = R.Run(cell["cell"], cell["hf"], {}, cell["mix"], args.seconds, ex["rec"], None, {})
    al = PS.aligned(run)
    if al is None:
        print("the program's steps do not pair with the harness's: all steps reported")
        spans, only = snap["spans"], None
    else:
        spans, only = al["spans"], {s[0] for s in PS.window_steps(run, al["steps"])}
    trace = load_trace(keep) if keep else None
    if trace is not None:
        trace["op_names"] = hlo
        shutil.rmtree(keep, ignore_errors=True)
    rep = report(spans, trace, only)
    rep.update(dropped=snap["dropped"], counters=snap["counters"], result=res)
    if args.span_cost:
        rep["span_cost"] = span_cost()
        print(f"span cost: {rep['span_cost']}")
    _print(rep)
    if args.fixture and trace and trace["spans"]:
        mid = statistics.median(a for n, a, _ in trace["spans"] if n == STEP)
        with open(args.fixture, "w") as f:
            json.dump(fixture(trace, mid), f)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rep, f, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
