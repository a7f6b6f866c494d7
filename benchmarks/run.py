"""Benchmark harness entry: one section per paper table/figure + the
framework-side (beyond-paper) benchmarks.

    PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import importlib
import sys
import time

SECTIONS = [
    ("Fig.11 Proactive PE Configuration", "benchmarks.fig11_proactive"),
    ("Fig.12 Peer-to-peer control network", "benchmarks.fig12_network"),
    ("Fig.13 Control network scaling", "benchmarks.fig13_scaling"),
    ("Fig.14 Agile PE Assignment", "benchmarks.fig14_agile"),
    ("Fig.15 Utilization effects", "benchmarks.fig15_utilization"),
    ("Fig.16 Network vs Agile balance", "benchmarks.fig16_balance"),
    ("Fig.17 vs state-of-the-art", "benchmarks.fig17_sota"),
    ("Table 6 Network area", "benchmarks.table6_area"),
    ("MoE route modes (framework)", "benchmarks.moe_modes"),
    ("Agile pipeline planning (framework)", "benchmarks.agile_pipeline"),
    ("Roofline (from dry-run artifacts)", "benchmarks.roofline"),
]


def main() -> int:
    failures = 0
    for title, module in SECTIONS:
        print(f"\n# {title}")
        t0 = time.time()
        try:
            importlib.import_module(module).main()
            print(f"# done in {time.time() - t0:.1f}s")
        except Exception as e:  # keep the harness running; report at the end
            failures += 1
            print(f"# FAILED: {type(e).__name__}: {e}")
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    sys.exit(main())
