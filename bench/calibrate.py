"""Readings that the benchmark's fixed numbers are set from, in one process
on the chip.  The benchmark's own runs never run this.

    python bench/calibrate.py --workload <cell> --seeds 101-112 \\
        --control-seeds 3 --seconds 10 --out chiprun_out/cal.jsonl
    python bench/calibrate.py --workload <cell> --seeds 201 --rates 8,12,16 \\
        --seconds 20 --out chiprun_out/sweep.jsonl

The first form serves the cell once per seed, as ``run.py`` does, and
writes the output check's numbers per seed; for the first
``--control-seeds`` seeds it also reads the control (the reference in fp8,
``bench/reference``) on the same sample.  The second form serves an open
loop mix at each offered rate and writes what the rate sweep needs: the
tails, the throughput and how many requests were still waiting for
admission when the window closed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1] / "src"),
                str(Path(__file__).resolve().parents[1])]

from bench import run as R  # noqa: E402
from bench import stats  # noqa: E402


def seeds_of(text: str):
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out.extend(range(int(a), int(b or a) + 1))
    return out


def summary(res, ex, seconds):
    rec = ex["rec"]
    ttft = stats.ttfts(rec.due, {r: ts[0] for r, ts in rec.tokens.items() if ts}, seconds)
    itl = stats.gaps(rec.tokens, seconds)
    late = sum(1 for r, d in rec.due.items()
               if 0 <= d < seconds and (r not in rec.admit or rec.admit[r][0] >= seconds))
    return {
        "correct": res["correct"], "check": res["check"], "control": ex["verdict"].get("control"),
        "sampled": ex["verdict"]["sampled"], "gap_stats": ex["verdict"]["stats"],
        "sampled_tokens": sum(len(rec.finished[r]) for r in ex["verdict"]["sampled"]),
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "ttft_p50_ms": (stats.percentile(ttft, 50) or 0) * 1e3,
        "ttft_p95_ms": (stats.percentile(ttft, 95) or 0) * 1e3,
        "itl_p50_ms": (stats.percentile(itl, 50) or 0) * 1e3,
        "itl_p95_ms": (stats.percentile(itl, 95) or 0) * 1e3,
        "tokens_per_s": stats.tokens_in(rec.tokens, seconds) / seconds,
        "due_in_window": sum(1 for d in rec.due.values() if 0 <= d < seconds),
        "waiting_at_close": late,
        "steps": len(rec.steps), "admissions": len(rec.admit),
        "memory_peak_bytes": res["device"]["memory_peak_bytes"],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112 or 5,9,13")
    ap.add_argument("--control-seeds", type=int, default=0)
    ap.add_argument("--rates", default="", help="offered rates (open loop mixes)")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    R.use_compile_cache()
    c = R.load_cell(args.workload)
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    with open(args.out, "a") as out:
        for i, seed in enumerate(seeds_of(args.seeds)):
            for rate in rates:
                if rate is not None:
                    c["mix"]["arrival"]["rate_per_s"] = rate
                ex = {}
                t0 = time.perf_counter()
                res = R.run_cell(c, seed, args.seconds, False, extras=ex,
                                 control="fp8" if i < args.control_seeds else "")
                if res is None:
                    return 1
                line = dict(workload=args.workload, seed=seed, rate=rate,
                            seconds=args.seconds, wall_s=time.perf_counter() - t0,
                            **summary(res, ex, args.seconds))
                out.write(json.dumps(line) + "\n")
                out.flush()
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
