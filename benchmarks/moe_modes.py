"""Beyond-paper framework benchmark: compiled FLOPs + control bytes of the
three MoE route modes (predication / coupled / proactive) on the smoke
config — the paper's Fig. 3 pathology measured in XLA artifacts, plus the
wall-clock of the three modes on CPU (directional only)."""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.configs import get_smoke_config
from repro.core.control_plane import capacity_for, route_topk
from repro.models import moe as moe_mod


def run() -> list:
    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    cfg = dataclasses.replace(cfg, top_k=2, capacity_factor=1.5)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, cfg.d_model))
    rows = []
    for mode in ("dense", "sync", "lookahead"):
        c = dataclasses.replace(cfg, route_mode=mode)
        rs = x if mode == "lookahead" else None
        fn = jax.jit(lambda xx, m=c, r=rs: moe_mod.moe_layer(xx, r if r is not None else None, p, m)[0])
        cost = fn.lower(x).compile().cost_analysis()
        flops = cost.get("flops", 0.0)
        fn(x)  # warm
        t0 = time.perf_counter()
        for _ in range(10):
            fn(x).block_until_ready()
        us = (time.perf_counter() - t0) / 10 * 1e6
        T = x.shape[0] * x.shape[1]
        plan, _ = route_topk(
            x.reshape(T, -1), p["router"], c.top_k,
            capacity_for(T, c.num_experts, c.top_k, c.capacity_factor),
        )
        rows.append(
            {
                "route_mode": mode,
                "hlo_flops": flops,
                "us_per_call": us,
                "control_plane_bytes": plan.control_bytes(),
                "data_bytes": x.size * x.dtype.itemsize,
            }
        )
    return rows


def main() -> None:
    emit(run())


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
