"""Kernel-only timing of ``flash_decode`` over the cache as the model stores
it, (B, S, nkv, hd), at several head widths and KV block sizes: the source
of the block-size rule (``decode._block_rows``).

    python tools/flash_decode_sweep.py --out chiprun_out/fd_sweep.json
    python tools/flash_decode_sweep.py --baseline ../other-checkout   # also time its flash_decode

For each width (``nq x nkv x hd``) it times, on B slots of S cache rows in
bf16, one ``flash_decode`` call with the shape-chosen block (``auto``) and
with each block of ``--blocks``, under two length patterns: ``mixed``
(lengths drawn from [S/10, S)) and ``full`` (every slot at S).  Each time is
the mean of ``--iters`` back-to-back calls, on the host clock, after one
compiling call.  ``roofline_pct`` is the live K and V bytes over the time,
against the v5e's 819 GB/s.  ``max_err`` is against ``ref.flash_decode`` in
f32.  With ``--baseline`` the ``flash_decode`` of that checkout's
``src/repro/kernels/flash_attention/decode.py`` is timed on the same arrays.
A block that does not compile is recorded with its error.  Times mean
something only on the chip; on the CPU the kernels run in the Pallas
interpreter (use small ``--slots``/``--rows`` there, as a rehearsal).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

HBM_BYTES_PER_S = 819e9  # TPU v5e
# qwen3-32b, qwen3-moe-235b-a22b on one chip and per chip of four,
# starcoder2-3b, qwen1.5-4b, phi-3-vision
WIDTHS = "64x8x128,64x4x128,16x1x128,24x2x128,20x20x128,32x32x96"


def load_baseline(checkout: str):
    path = Path(checkout) / "src/repro/kernels/flash_attention/decode.py"
    spec = importlib.util.spec_from_file_location("baseline_decode", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def time_call(fn, args, iters: int) -> float:
    fn(*args).block_until_ready()
    t = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t) / iters


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--rows", type=int, default=3073, help="cache rows S")
    ap.add_argument("--widths", default=WIDTHS, help="comma-separated nq x nkv x hd")
    ap.add_argument("--blocks", default="128,256,512", help="comma-separated block rows")
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--baseline", help="checkout whose flash_decode is timed too")
    ap.add_argument("--out", help="write the results here as JSON")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention import decode, ref

    base = load_baseline(args.baseline) if args.baseline else None
    print(jax.devices()[0].device_kind, flush=True)
    B, S = args.slots, args.rows
    rng = np.random.default_rng(7)
    patterns = {"mixed": rng.integers(max(1, S // 10), S, B), "full": np.full(B, S)}
    res = {}
    for width in args.widths.split(","):
        nq, nkv, hd = (int(x) for x in width.split("x"))
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (B, 1, nq, hd), jnp.bfloat16)
        kc = jax.random.normal(k2, (B, S, nkv, hd), jnp.bfloat16)
        vc = jax.random.normal(k3, (B, S, nkv, hd), jnp.bfloat16)
        fns = {f"auto{decode._block_rows(S, nkv, hd, kc.dtype)}":
               jax.jit(lambda q, k, v, i: decode.flash_decode(q, k, v, i))}
        for bkv in (int(b) for b in args.blocks.split(",")):
            fns[str(bkv)] = jax.jit(lambda q, k, v, i, bkv=bkv: decode.flash_decode(q, k, v, i, bkv=bkv))
        if base is not None:
            fns["baseline"] = jax.jit(lambda q, k, v, i: base.flash_decode(q, k, v, i))
        for pattern, lens in patterns.items():
            idx = jnp.asarray(lens - 1, jnp.int32)
            live = float(lens.sum()) * 2 * nkv * hd * kc.dtype.itemsize
            want = jax.jit(lambda q, k, v, i: ref.flash_decode(q, k, v, i))(q, kc, vc, idx)
            for name, fn in fns.items():
                key = f"{width}.{pattern}.{name}"
                try:
                    s = time_call(fn, (q, kc, vc, idx), args.iters)
                except Exception as e:  # noqa: BLE001 - a block that does not compile is a result
                    res[key] = {"error": str(e).splitlines()[0][:300]}
                else:
                    err = float(jnp.max(jnp.abs(fn(q, kc, vc, idx).astype(jnp.float32) - want)))
                    res[key] = {"ms": s * 1e3, "roofline_pct": live / HBM_BYTES_PER_S / s * 100,
                                "max_err": err}
                print(key, res[key], flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(res, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
