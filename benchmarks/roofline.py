"""Roofline table: reads the dry-run artifacts (results/dryrun/*.json) and
emits the per-(arch x shape) three-term roofline for the single-pod mesh,
plus the control-plane byte share (the Table-6 analogue).

Run the dry-run first:  PYTHONPATH=src python -m repro.launch.dryrun --all
"""
from __future__ import annotations

import json
from pathlib import Path

from benchmarks.common import emit

DRYRUN_DIR = Path(__file__).resolve().parent.parent / "results" / "dryrun"


def load_records(pod: str = "pod1"):
    recs = []
    for f in sorted(DRYRUN_DIR.glob(f"*__{pod}.json")):
        recs.append(json.loads(f.read_text()))
    return recs


def run() -> list:
    rows = []
    for r in load_records("pod1"):
        if r["status"] == "skipped":
            rows.append(
                {
                    "arch": r["arch"], "cell": r["cell"], "status": "skipped",
                    "compute_s": 0.0, "memory_s": 0.0, "collective_s": 0.0,
                    "bottleneck": "-", "roofline_fraction": 0.0,
                    "useful_flop_ratio": 0.0, "control_share": 0.0,
                }
            )
            continue
        if r["status"] != "ok":
            rows.append(
                {
                    "arch": r["arch"], "cell": r["cell"], "status": "ERROR",
                    "compute_s": 0.0, "memory_s": 0.0, "collective_s": 0.0,
                    "bottleneck": "-", "roofline_fraction": 0.0,
                    "useful_flop_ratio": 0.0, "control_share": 0.0,
                }
            )
            continue
        roof = r["roofline"]
        rows.append(
            {
                "arch": r["arch"],
                "cell": r["cell"],
                "status": "ok",
                "compute_s": roof["compute_s"],
                "memory_s": roof["memory_s"],
                "collective_s": roof["collective_s"],
                "bottleneck": roof["bottleneck"].replace("_s", ""),
                "roofline_fraction": roof["roofline_fraction"],
                "useful_flop_ratio": roof["useful_flop_ratio"],
                "control_share": roof["control_share_of_wire"],
            }
        )
    return rows


def main() -> None:
    if not DRYRUN_DIR.exists():
        print("roofline: no dry-run artifacts found; run repro.launch.dryrun --all first")
        return
    emit(run())


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
