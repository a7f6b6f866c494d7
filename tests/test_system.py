"""End-to-end behaviour of the paper's system: the control-flow-plane modes
produce the documented FLOP/latency trade-offs, and the full framework train
path (model + control plane + optimizer + data) learns on CPU."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_platform_name", "cpu")


def _count_flops(fn, *args):

    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def test_predication_costs_more_flops_than_dispatch():
    """The paper's core pathology, measured in the compiled artifact: the
    predication baseline (dense route_mode — both branch lanes execute)
    spends ~E/k times the expert FLOPs of the plan-dispatched path."""
    from repro.configs import get_smoke_config
    from repro.models import moe as moe_mod

    cfg = get_smoke_config("qwen3-moe-235b-a22b")  # 8 experts in the smoke cfg
    cfg = dataclasses.replace(cfg, top_k=2, capacity_factor=1.25)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))

    f_dense = _count_flops(
        lambda xx: moe_mod.moe_layer(xx, None, p, dataclasses.replace(cfg, route_mode="dense"))[0], x
    )
    f_sparse = _count_flops(
        lambda xx: moe_mod.moe_layer(xx, None, p, dataclasses.replace(cfg, route_mode="sync"))[0], x
    )
    # 8 experts vs top-2 with capacity slack: expect >= 2x FLOPs for predication
    assert f_dense > 2.0 * f_sparse


def test_quickstart_training_learns():
    """~1M-param model, 60 steps on the Markov stream: loss must drop well
    below the unigram floor (log V) — the framework actually trains."""
    import tempfile

    from repro.configs import get_smoke_config
    from repro.configs.base import ShapeCell
    from repro.launch.mesh import make_host_mesh
    from repro.runtime import Trainer, TrainerConfig

    cfg = get_smoke_config("qwen1.5-4b")
    cell = ShapeCell("t", seq_len=64, global_batch=8, step="train")
    with tempfile.TemporaryDirectory() as td:
        tr = Trainer(
            cfg, cell, make_host_mesh(1, 1),
            TrainerConfig(num_steps=60, checkpoint_every=1000, checkpoint_dir=td,
                          log_every=1000, lr=3e-3),
        )
        out = tr.run()
    losses = [m["ce"] for m in out["metrics"]]
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])
    assert losses[-1] < np.log(cfg.vocab_size)


def test_lookahead_plan_quality_degrades_gracefully():
    """Lookahead routes layer l's tokens with the *previous* residual stream.
    The plan differs from the sync plan only where the residual update flips
    the top-k decision; with a small residual delta the disagreement rate
    must be small (the Proactive-Configuration bet, quantified)."""
    from repro.core.control_plane import capacity_for, route_topk

    rng = np.random.default_rng(0)
    T, d, E, k = 256, 64, 16, 2
    h = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    delta = 0.05 * jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    wr = jnp.asarray(rng.standard_normal((d, E)) * 0.5, jnp.float32)
    C = capacity_for(T, E, k, 1.25)
    plan_sync, _ = route_topk(h + delta, wr, k, C)
    plan_look, _ = route_topk(h, wr, k, C)
    same = 0.0
    for t in range(T):
        e_sync = set(int(i) // C for i in np.asarray(plan_sync.combine_idx[t]) if i >= 0)
        e_look = set(int(i) // C for i in np.asarray(plan_look.combine_idx[t]) if i >= 0)
        same += len(e_sync & e_look) / max(len(e_sync | e_look), 1)
    agreement = same / T
    assert agreement > 0.8, agreement
