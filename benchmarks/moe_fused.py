"""Fused vs unfused MoE data plane: XLA-reported FLOPs, bytes-accessed, and
wall-clock per layer, across the three route modes and two MoE model families.

Three data planes execute the same plan:

* ``reference`` — pure-jnp dispatch -> grouped SwiGLU -> combine (the
  model-default CPU path).
* ``unfused``   — the three-launch Pallas pipeline: ``dispatch_pallas``,
  ``grouped_gemm_pallas`` (x3 inside grouped SwiGLU), ``combine_pallas``;
  each stage round-trips the (E, C, d) slot tensors through memory.
* ``fused``     — kernels/moe_fused: plan-steered gather -> grouped GEMM ->
  scatter in two launches; no (E, C, d) tensor is ever materialized.

``ecd_intermediates`` counts (E, C, d)-shaped tensors in the lowered HLO —
the acceptance signal that the round-trips are actually gone (0 on fused
rows).  ``dense`` mode is the predication baseline (no dispatch to fuse) and
is reported reference-only for scale.  Numbers come from the CPU
interpret-mode lowering, so wall-clock is directional only; the
bytes-accessed ordering fused < unfused matches the HBM traffic a TPU pays
(two launch boundaries instead of five).

    PYTHONPATH=src python -m benchmarks.moe_fused
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.configs import get_smoke_config
from repro.core.control_plane import capacity_for, route_topk
from repro.models import moe as moe_mod

CONFIGS = ("qwen3-moe-235b-a22b", "llama4-maverick-400b-a17b")
BATCH, SEQ = 4, 64


def _data_plane_fn(cfg, p, C, plane: str, mode: str):
    """(T, d) -> (T, d) one-MoE-layer closure for the chosen data plane."""
    top_k = cfg.top_k

    def route(xx):
        return route_topk(xx, p["router"], top_k, C)[0]

    if plane == "reference":

        def fn(xx, rs):
            c = dataclasses.replace(cfg, route_mode=mode)
            y, _ = moe_mod.moe_ffn(
                xx[None], p, c, plan=route(rs) if mode == "lookahead" else None, fused=False
            )
            return y[0]

    elif plane == "unfused":
        from repro.kernels.grouped_gemm import ops as gops
        from repro.kernels.moe_dispatch import ops as dops

        def fn(xx, rs):
            plan = route(rs if mode == "lookahead" else xx)
            slots = dops.dispatch(xx, plan)
            y_slots = gops.grouped_swiglu(slots, p["w_gate"], p["w_up"], p["w_down"])
            return dops.combine(y_slots, plan)

    else:  # fused
        from repro.kernels.moe_fused import ops as fops

        def fn(xx, rs):
            plan = route(rs if mode == "lookahead" else xx)
            return fops.fused_moe_fn(xx, plan, p)

    return fn


def _bench(cfg, p, x, rs, plane: str, mode: str) -> dict:
    T = x.shape[0]
    C = capacity_for(T, cfg.num_experts, cfg.top_k, cfg.capacity_factor)
    if mode == "dense":
        c = dataclasses.replace(cfg, route_mode="dense")
        fn = jax.jit(lambda xx, r: moe_mod.moe_ffn(xx[None], p, c)[0][0])
    else:
        fn = jax.jit(_data_plane_fn(cfg, p, C, plane, mode))
    lowered = fn.lower(x, rs)
    cost = lowered.compile().cost_analysis()
    n_ecd = lowered.as_text().count(f"tensor<{cfg.num_experts}x{C}x{cfg.d_model}x")
    fn(x, rs)  # warm
    t0 = time.perf_counter()
    for _ in range(5):
        fn(x, rs).block_until_ready()
    us = (time.perf_counter() - t0) / 5 * 1e6
    return {
        "config": cfg.name,
        "route_mode": mode,
        "data_plane": plane,
        "hlo_flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "ecd_intermediates": n_ecd,
        "us_per_call": us,
    }


def run() -> list:
    rows = []
    for name in CONFIGS:
        cfg = get_smoke_config(name)
        cfg = dataclasses.replace(cfg, top_k=min(2, cfg.top_k or 2), capacity_factor=1.5)
        p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (BATCH * SEQ, cfg.d_model))
        rs = jax.random.normal(jax.random.PRNGKey(2), (BATCH * SEQ, cfg.d_model))
        rows.append(_bench(cfg, p, x, rs, "reference", "dense"))
        for mode in ("sync", "lookahead"):
            for plane in ("reference", "unfused", "fused"):
                rows.append(_bench(cfg, p, x, rs, plane, mode))
    return rows


def main() -> None:
    rows = run()
    emit(rows)
    for r_un in rows:
        if r_un["data_plane"] != "unfused":
            continue
        (r_fu,) = [
            r
            for r in rows
            if r["data_plane"] == "fused"
            and r["config"] == r_un["config"]
            and r["route_mode"] == r_un["route_mode"]
        ]
        saved = r_un["bytes_accessed"] - r_fu["bytes_accessed"]
        print(
            f"# {r_un['config']} {r_un['route_mode']}: fused retires "
            f"{saved / 1e6:.2f} MB/layer vs the three-launch path "
            f"({r_un['ecd_intermediates']} -> {r_fu['ecd_intermediates']} (E,C,d) intermediates)"
        )


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
