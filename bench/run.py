"""Run one cell of ``BENCHMARK.json`` once, on the chips JAX finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``) and a traffic
mix (``bench/traffic/<mix>.json``); its output check's limits are in
``bench/limits/<cell>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  Nothing here names a cell, a mix or a
metric: a new one is new files and new ``BENCHMARK.json`` entries.

The run makes the weights on the device from the seed, builds the program's
serve replica (``launch.serve.make_replica_factory``), warms up every prompt
bucket of the mix and the decode step, fills the slots of a closed loop (or
ramps an open loop up), and then serves the mix for ``--seconds``.  With
``--trace 0`` it reports the cell's end-to-end metrics; with ``--trace 1``
it profiles a stretch of the window and reports the per-layer metrics.
Once the window has closed and the program's state is freed, it checks what
was served against the plain reference (``bench/check.py``).

The last line of standard output is the result as one JSON object.  The run
exits nonzero, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the program cannot be imported.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# part of the traced stretch of a --trace 1 window: from this share of the
# window to that one
TRACE_FROM, TRACE_TO = 0.5, 0.65
# an open loop's requests due in the window get their first token within
# this many seconds after it closes, or count as failed
DRAIN_S = 60.0
# program fields the configuration file's published keys must agree with
FIELDS = {
    "hidden_size": "d_model", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
    "num_experts": "num_experts", "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "d_ff_expert", "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Dict:
    """The cell's entry, its configuration file, its mix and its limits."""
    spec = read_json(ROOT / "BENCHMARK.json")
    cell = next((w for w in spec["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    return {
        "spec": spec, "cell": cell,
        "hf": read_json(ROOT / conf["file"]),
        "mix": read_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "limits": read_json(BENCH / "limits" / f"{name}.json"),
    }


def device_guard(chips: int):
    """The devices, or None when JAX finds no TPU or too few chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: JAX finds no TPU (platform {devices[0].platform!r})")
        return None
    if len(devices) < chips:
        log(f"bench: the cell needs {chips} chips, JAX finds {len(devices)}")
        return None
    return devices


def program_config(hf: Dict):
    """The program's ModelConfig: the registered preset the file names, with
    each override applied where the field exists; then every published key
    the program has a field for must agree with it.  For a share of the
    experts ``reduced_from.num_experts`` is the router's width, the
    program's ``num_experts``; where the program has the fields, the file's
    held ``num_experts`` must be its ``experts_held`` (0: every expert) and
    the file's ``expert_share`` (default 0) its ``expert_share``."""
    from repro.configs import get_config

    prog = hf["program"]
    base = get_config(prog["preset"])
    names = {f.name for f in dataclasses.fields(base)}
    over = {k: v for k, v in prog.get("overrides", {}).items() if k in names}
    skipped = sorted(set(prog.get("overrides", {})) - set(over))
    if skipped:
        log(f"bench: overrides skipped (no such field in the program): {skipped}")
    cfg = dataclasses.replace(base, **over)
    router = hf.get("reduced_from", {}).get("num_experts")
    want = dict(hf, num_experts=router) if router else hf
    wrong = []
    for key, field in FIELDS.items():
        if key in want and field in names:
            got = cfg.resolved_head_dim if field == "head_dim" else getattr(cfg, field)
            if got != want[key]:
                wrong.append(f"{key}={want[key]} but {field}={got}")
    if hf.get("num_experts"):
        held = hf["num_experts"]
        if "experts_held" in names and (cfg.experts_held or cfg.num_experts) != held:
            wrong.append(f"num_experts={held} held but the program holds "
                         f"experts_held={cfg.experts_held} of num_experts={cfg.num_experts}")
        share = hf.get("expert_share", 0)
        if "expert_share" in names and cfg.expert_share != share:
            wrong.append(f"expert_share={share} but expert_share={cfg.expert_share}")
    elif "d_ff" in names and cfg.d_ff != hf["intermediate_size"]:
        wrong.append(f"intermediate_size={hf['intermediate_size']} but d_ff={cfg.d_ff}")
    if wrong:
        raise SystemExit(f"bench: the program does not run the configuration: {wrong}")
    return cfg


def build_replica(cfg, hf: Dict, seed: int, slots: int, max_len: int):
    """Weights drawn from the seed on the device in one jitted call, in the
    program's dtypes and shardings, handed to the program's replica."""
    import jax

    from bench import weights as W
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serve import make_replica_factory
    from repro.models.transformer import init_params
    from repro.parallel.sharding import param_shardings

    mesh = make_host_mesh(1, 1)
    dims = W.dims_of(hf)
    abstract = jax.eval_shape(lambda k: init_params(k, cfg), jax.ShapeDtypeStruct((2,), "uint32"))
    shard = param_shardings(abstract, mesh)
    make = jax.jit(lambda k: W.program_params(k, abstract, dims, len(cfg.block_pattern)),
                   out_shardings=shard)
    params = make(W.seed_key(seed))
    factory = make_replica_factory(cfg, mesh, slots, max_len, params, [(None, 1)])
    return factory(0, 0)


def warm_up(rep, mix: Dict, vocab: int) -> None:
    """Compile every program the window will call: the prefill of each
    prompt bucket, the slot write and the decode step."""
    import numpy as np

    from repro.runtime.fabric import Request

    for i, b in enumerate(sorted(mix["prompt"]["buckets"])):
        prompt = (np.arange(b, dtype=np.int32) * 7 + i) % vocab
        rep.admit(Request(rid=-1 - i, prompt=prompt, gen=1))
        while rep.has_work():
            rep.step()


def compile_counter():
    """Number of backend compiles JAX reports from now on."""
    from jax import monitoring

    n = [0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    monitoring.register_event_duration_secs_listener(listen)
    return n


def use_compile_cache() -> None:
    """The program's compile cache (``$JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``), keeping every program, however quick to
    compile.  Call before JAX is imported."""
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    from repro.compile_cache import use_compile_cache as program_cache

    program_cache()


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a per-layer reader reads: the run's records on the window's
    clock, the trace's reduction (or None) and the device's peaks."""

    cell: Dict
    hf: Dict
    dims: Dict
    mix: Dict
    seconds: float
    rec: object
    trace: Optional[Dict]
    peaks: Dict


def end_to_end(spec: Dict, cell: Dict, rec, seconds: float) -> Dict:
    from bench import stats

    values = {
        "ttft_p95_ms": lambda: stats.percentile(stats.ttfts(rec.due, {
            r: ts[0] for r, ts in rec.tokens.items() if ts}, seconds), 95),
        "itl_p95_ms": lambda: stats.percentile(stats.gaps(rec.tokens, seconds), 95),
        "output_tokens_per_s": lambda: stats.tokens_in(rec.tokens, seconds) / seconds,
    }
    out = {}
    for m in spec["end_to_end"]:
        if m["name"] == "setup_s" or cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        v = values[m["name"]]()
        if v is not None:
            scale = 1e3 if m["unit"] == "ms" else 1.0
            out[m["name"]] = {"value": v * scale, "unit": m["unit"]}
    return out


def per_layer(spec: Dict, run: Run, e2e_names) -> Dict:
    out = {}
    for m in spec["per_layer"]:
        cells = m.get("workloads")
        if cells is None and m["moves"] not in e2e_names:
            continue
        if cells is not None and run.cell["name"] not in cells:
            continue
        v = load_reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def cell_e2e_names(spec: Dict, cell: Dict):
    return {m["name"] for m in spec["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])}


def run_cell(c: Dict, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, on_replica=None, keep_trace: str = "",
             control: str = "", extras: Optional[Dict] = None) -> Optional[Dict]:
    """One run of a cell; None when the devices do not serve it.

    ``on_replica(rep)`` sees the program's replica before the warm-up (tests
    plant faults there); ``control`` also reads the output check's control
    on the same sample; ``extras`` receives the run's records."""
    spec, cell, hf, mix, limits = c["spec"], c["cell"], c["hf"], c["mix"], c["limits"]
    import jax

    devices = device_guard(cell["chips"]) if require_tpu else jax.devices()
    if devices is None:
        return None
    print(f"device_kind {devices[0].device_kind}, {len(devices)} device(s), "
          f"compile cache {jax.config.jax_compilation_cache_dir}", flush=True)

    from bench import check as CK
    from bench import traffic as TR
    from bench import weights as W
    from bench.loop import Loop, Recorder, Traffic

    peaks = read_json(BENCH / "peaks.json").get(devices[0].device_kind)
    if peaks is None and require_tpu:
        raise SystemExit(f"bench: no peaks for device kind {devices[0].device_kind!r}")
    cfg = program_config(hf)
    dims = W.dims_of(hf)
    slots = int(hf["program"]["slots"])
    sched = TR.generate(mix, seed, dims["vocab"], seconds=seconds, slots=slots)
    compiles = compile_counter()
    marks = [("start", T_START), ("devices", time.perf_counter())]
    rep = build_replica(cfg, hf, seed, slots, sched.max_len)
    marks.append(("weights and build", time.perf_counter()))
    if on_replica is not None:
        on_replica(rep)
    warm_up(rep, mix, dims["vocab"])
    marks.append(("warm-up", time.perf_counter()))

    rec = Recorder()
    clock = time.perf_counter
    if sched.closed:
        traffic = Traffic(sched, clock())
        for _ in range(sched.concurrency):
            traffic.finished(clock(), rec)  # the first cohort is due at once
        loop = Loop(rep, traffic, rec, clock)
        loop.admit_from_queue()
        t0 = clock()
        setup_s = t0 - T_START
    else:
        ramp = float(mix["arrival"].get("ramp_s", 0.0))
        setup_s = clock() - T_START + ramp
        t0 = clock() + ramp
        traffic = Traffic(sched, t0)
        loop = Loop(rep, traffic, rec, clock)
        loop.run(t0)
    compiles_before = compiles[0]
    marks.append(("fill or ramp", t0))
    log("bench: set-up " + ", ".join(f"{n} {b - a:.3f} s" for (_, a), (n, b) in zip(marks, marks[1:]))
        + f"; {compiles[0]} compiles")
    t_end = t0 + seconds
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    state = {"on": False, "done": not trace}

    def on_round(now):
        if state["done"]:
            return
        if not state["on"] and now >= t0 + TRACE_FROM * seconds:
            jax.profiler.start_trace(trace_dir)
            state["on"] = True
            rec.marks["trace_on"] = clock()
        elif state["on"] and now >= t0 + TRACE_TO * seconds:
            rec.marks["trace_off"] = clock()
            jax.profiler.stop_trace()
            state["on"], state["done"] = False, True

    loop.run(t_end, on_round=on_round)
    if state["on"]:
        rec.marks["trace_off"] = clock()
        jax.profiler.stop_trace()
    in_window_compiles = compiles[0] - compiles_before
    if not sched.closed:
        # the requests due in the window get their first tokens
        traffic.pending.clear()
        waiting = lambda: any(r not in rec.admit and r not in rec.rejected
                              for r, d in rec.due.items() if d < t_end)
        loop.run(t_end + DRAIN_S, stop=lambda: not waiting())
    stats_mem = devices[0].memory_stats() or {}
    peak = int(stats_mem.get("peak_bytes_in_use", 0))
    rec.relative_to(t0)

    prompts = {r.rid: r.prompt for r in sched.requests}
    outputs = {r.rid: r.output for r in sched.requests}
    del rep, loop, traffic
    gc.collect()

    due_in = [r for r, d in rec.due.items() if 0.0 <= d < seconds]
    in_flight = [r for r, (_, b, _) in rec.admit.items()
                 if b < 0.0 and (r not in rec.finished or rec.tokens[r][-1] >= 0.0)]
    attempted = len(set(due_in) | set(in_flight))
    failed = sum(1 for r in due_in if r in rec.rejected or not rec.tokens.get(r))

    e2e_names = cell_e2e_names(spec, cell)
    if trace:
        from bench import trace as TRC

        ops, spans = TRC.load(trace_dir)
        reduced = TRC.reduce(ops, spans) if ops else None
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run = Run(cell, hf, dims, mix, seconds, rec, reduced, peaks or {})
        metrics = per_layer(spec, run, e2e_names)
    else:
        metrics = end_to_end(spec, cell, rec, seconds)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        reduced = None

    t_check = time.perf_counter()
    verdict = CK.check(hf, seed, rec.finished, prompts, limits, outputs, control=control)
    check_s = time.perf_counter() - t_check
    if extras is not None:
        extras.update(rec=rec, prompts=prompts, verdict=verdict)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    result = {"correct": verdict["correct"], "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace and reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    log(f"bench: {len(rec.steps)} steps, {len(rec.admit)} admissions, "
        f"{len(rec.finished)} finished, {in_window_compiles} compiles in the window, "
        f"setup {setup_s:.3f} s")
    log(f"bench: checked {len(verdict['sampled'])} requests, "
        f"{sum(len(rec.finished[x]) for x in verdict['sampled'])} served tokens in "
        f"{check_s:.3f} s; gaps " + json.dumps(verdict["stats"]))
    if "control" in verdict:
        for name, v in verdict["control"]["numbers"].items():
            log(f"control {name} {v['value']!r} limit {v['limit']!r}")
        log(f"control correct {verdict['control']['correct']}")
    for name, v in verdict["numbers"].items():
        log(f"check {name} {v['value']!r} limit {v['limit']!r}")
    result["check"] = verdict["numbers"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="also copy the profiler's trace directory here")
    args = ap.parse_args(argv)
    use_compile_cache()
    c = load_cell(args.workload)
    result = run_cell(c, args.seed, args.seconds, bool(args.trace), keep_trace=args.keep_trace)
    if result is None:
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
