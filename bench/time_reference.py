"""Time the output check's reference on the chip: ``logits_at`` over one
sequence of a configuration, once with whatever the compile cache holds
(empty: the first run of a cell in a checkout) and once more with it warm.
The benchmark's own runs never run this.

    JAX_COMPILATION_CACHE_DIR=<empty dir> python bench/time_reference.py \\
        --config bench/configs/qwen3-32b-1chip.json \\
        --config bench/tests/data/qwen3-30b-a3b.6l.json --tokens 3072

Prints one JSON line per configuration: the seconds of each call, the
seconds the backend spent compiling in it, and the device.  Exits nonzero
without a TPU.
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


SEED = 2**31 + 3


def time_one(hf, tokens: int, compiles) -> dict:
    """Two calls of the reference over one ``tokens``-long sequence, logits at
    its last two thirds (a prompt and the tokens served after it)."""
    import numpy as np

    from bench import weights as W
    from bench.reference import qwen3

    dims = W.dims_of(hf)
    seq = np.random.default_rng(SEED).integers(0, dims["vocab"], tokens, dtype=np.int32)
    pos = np.arange(tokens // 3, tokens)
    out = {"tokens": tokens, "layers": dims["layers"], "experts": dims["experts"],
           "router": dims["router"]}
    for name in ("cold", "warm"):
        c0, t0 = compiles["s"], time.perf_counter()
        logits = qwen3.logits_at(hf, SEED, [seq], [pos])
        out[f"{name}_s"] = time.perf_counter() - t0
        out[f"{name}_compile_s"] = compiles["s"] - c0
        out["finite"] = bool(np.isfinite(logits[0]).all())
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", action="append", required=True)
    ap.add_argument("--tokens", type=int, default=3072)
    args = ap.parse_args()
    R.use_compile_cache()
    import jax
    from jax import monitoring

    devices = R.device_guard(1)
    if devices is None:
        return 1
    compiles = {"s": 0.0}

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["s"] += duration

    monitoring.register_event_duration_secs_listener(listen)
    for path in args.config:
        res = time_one(R.read_json(Path(path)), args.tokens, compiles)
        res.update(config=path, device=devices[0].device_kind,
                   cache=jax.config.jax_compilation_cache_dir)
        print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
