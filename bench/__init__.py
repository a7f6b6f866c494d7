"""The chip benchmark of this repository: ``python bench/run.py --workload
<cell> --seed <n> --seconds <s> --trace <0|1>`` runs one cell of
``BENCHMARK.json`` on the chips JAX finds (see ``PERF.md``)."""
