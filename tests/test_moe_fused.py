"""Interpret-mode parity for the fused MoE data plane: the plan-steered
gather->GEMM and GEMM->scatter kernels must match the unfused
dispatch / grouped-SwiGLU / combine composition, including dropped-token and
ragged (non-128-multiple capacity) cases.

The gather-GEMM launch is asserted bit-for-bit in f32.  The scatter-combine
epilogue is asserted to ~1 ulp: XLA fuses the epilogue's weight-multiply +
accumulate into an FMA, which rounds once where the unfused composition
(multiply, then sum) rounds twice — tighter, but not bit-identical.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.control_plane import capacity_for, combine, dispatch, route_topk
from repro.kernels.moe_fused import ops, ref

jax.config.update("jax_platform_name", "cpu")

ULP = dict(rtol=1e-6, atol=1e-6)


def _case(T, d, E, k, f, cf, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    wr = jnp.asarray(rng.standard_normal((d, E)) * 0.1, jnp.float32)
    C = capacity_for(T, E, k, cf)
    plan, aux = route_topk(x, wr, k, C)
    p = {
        "w_gate": jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32),
        "w_up": jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32),
        "w_down": jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32),
    }
    return x, plan, aux, p, C


# capacity 24/40 are ragged (not 128-multiples); cf=0.5 forces drops
@pytest.mark.parametrize(
    "T,d,E,k,f,cf",
    [
        (64, 128, 4, 1, 128, 1.5),   # no-drop, aligned d
        (96, 64, 8, 2, 96, 1.25),    # ragged capacity + ragged f
        (80, 128, 4, 2, 64, 0.5),    # heavy drops
        (33, 96, 8, 4, 72, 1.0),     # ragged everything, k=4
    ],
)
def test_fused_gather_swiglu_bitexact(T, d, E, k, f, cf):
    """Fused gather + gate/up + SwiGLU == dispatch -> grouped SwiGLU oracle,
    bit-for-bit in f32 (same GEMM, same operands; the gather only changes
    where rows are read from)."""
    x, plan, aux, p, C = _case(T, d, E, k, f, cf)
    got = ops.fused_gather_swiglu(
        x, plan.flat_idx, p["w_gate"], p["w_up"], num_experts=E, capacity=C
    )
    want = ref.gather_swiglu(x, plan.flat_idx, p["w_gate"], p["w_up"])
    assert got.shape == (E, C, f)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if cf <= 0.5:
        assert float(aux.fraction_dropped) > 0  # the case really exercises drops


@pytest.mark.parametrize(
    "T,d,E,k,f,cf",
    [
        (64, 128, 4, 1, 128, 1.5),
        (96, 64, 8, 2, 96, 1.25),
        (80, 128, 4, 2, 64, 0.5),
        (33, 96, 8, 4, 72, 1.0),
    ],
)
def test_fused_down_combine_matches_unfused(T, d, E, k, f, cf):
    """Fused down-projection + weighted scatter == grouped GEMM -> combine."""
    x, plan, aux, p, C = _case(T, d, E, k, f, cf)
    h = ref.gather_swiglu(x, plan.flat_idx, p["w_gate"], p["w_up"])
    got = ops.fused_down_combine(
        h, p["w_down"], plan.flat_idx, plan.slot_w, num_tokens=T
    )
    y_slots = jnp.einsum("ecf,efd->ecd", h, p["w_down"])
    want = combine(y_slots, plan)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **ULP)
    # and against the slot-major oracle (same scatter order as the kernel)
    np.testing.assert_allclose(
        np.asarray(got),
        np.asarray(ref.down_combine(h, p["w_down"], plan.flat_idx, plan.slot_w, T)),
        **ULP,
    )


@pytest.mark.parametrize("T,d,E,k,f,cf", [(96, 64, 8, 2, 96, 1.25), (80, 128, 4, 2, 64, 0.5)])
def test_fused_pipeline_matches_unfused_composition(T, d, E, k, f, cf):
    """End-to-end: two fused launches == dispatch -> grouped SwiGLU -> combine."""
    x, plan, _, p, C = _case(T, d, E, k, f, cf)
    got = ops.fused_moe_fn(x, plan, p)
    slots = dispatch(x, plan)
    g = jnp.einsum("ecd,edf->ecf", slots, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", slots, p["w_up"])
    y_slots = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"])
    want = combine(y_slots, plan)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **ULP)


def test_moe_ffn_fused_matches_reference_data_plane():
    """moe_ffn(fused=True) == moe_ffn(fused=False) in both routed modes."""
    from repro.configs import get_smoke_config
    from repro.models import moe as moe_mod

    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    cfg = dataclasses.replace(cfg, top_k=2, capacity_factor=1.25)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, cfg.d_model))
    for mode in ("sync", "lookahead"):
        c = dataclasses.replace(cfg, route_mode=mode)
        rs = x if mode == "lookahead" else None
        y_ref, _ = moe_mod.moe_layer(x, rs, p, c, fused=False)
        y_fused, _ = moe_mod.moe_layer(x, rs, p, c, fused=True)
        np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_fused), **ULP)


def test_fused_hlo_has_no_ecd_intermediates():
    """The whole point: the fused lowering must not materialize any
    (E, C, d)-shaped tensor (the dispatch output / expert output round-trips
    the unfused path pays), while the unfused lowering does."""
    from repro.configs import get_smoke_config
    from repro.models import moe as moe_mod

    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    cfg = dataclasses.replace(cfg, route_mode="sync", top_k=2, capacity_factor=1.25)
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, cfg.d_model))
    T = 2 * 48
    from repro.core.control_plane import capacity_for as _cap

    C = _cap(T, cfg.num_experts, cfg.top_k, cfg.capacity_factor)
    ecd = f"tensor<{cfg.num_experts}x{C}x{cfg.d_model}x"

    def lowered(fused):
        fn = jax.jit(lambda xx: moe_mod.moe_ffn(xx, p, cfg, fused=fused)[0])
        return fn.lower(x).as_text()

    assert ecd in lowered(False)  # unfused pays the (E, C, d) round-trips
    assert ecd not in lowered(True)  # fused never forms the tensor


def test_plan_flat_tensors_consistent():
    """The flat SMEM-ready control words emitted by make_dispatch_plan agree
    with the 2-D plan views they replace."""
    x, plan, _, _, C = _case(80, 64, 8, 2, 32, 0.75, seed=7)
    E = plan.num_experts
    T = plan.combine_idx.shape[0]
    np.testing.assert_array_equal(
        np.asarray(plan.flat_idx),
        np.asarray(jnp.where(plan.dispatch_valid, plan.dispatch_idx, T).reshape(-1)),
    )
    np.testing.assert_array_equal(
        np.asarray(plan.flat_cidx),
        np.asarray(jnp.where(plan.combine_idx >= 0, plan.combine_idx, E * C).reshape(-1)),
    )
    np.testing.assert_array_equal(
        np.asarray(plan.flat_cw), np.asarray(plan.combine_w.reshape(-1))
    )
    # slot_w is the slot-major dual of combine_w
    cidx = np.asarray(plan.combine_idx).reshape(-1)
    cw = np.asarray(plan.combine_w).reshape(-1)
    slot_w = np.asarray(plan.slot_w)
    for s, w in zip(cidx, cw):
        if s >= 0:
            assert slot_w[s] == w
    assert slot_w[np.asarray(plan.dispatch_valid).reshape(-1) == 0].sum() == 0.0


def test_fraction_dropped_counts_slots_not_weights():
    """A zero router weight on a *placed* assignment must not count as a
    drop; only assignments without a slot (combine_idx < 0) do."""
    from repro.core.control_plane import make_dispatch_plan

    ids = jnp.asarray([[0], [0], [1]], jnp.int32)
    w = jnp.asarray([[0.0], [1.0], [1.0]], jnp.float32)  # token 0: weight 0
    plan = make_dispatch_plan(ids, w, num_experts=2, capacity=2)
    # all three assignments got slots -> nothing dropped
    assert (np.asarray(plan.combine_idx) >= 0).all()
    x = jnp.ones((3, 8), jnp.float32)
    wr = jnp.zeros((8, 2), jnp.float32)
    _, aux = route_topk(x, wr, 1, capacity=8)
    assert float(aux.fraction_dropped) == 0.0


def test_capacity_for_exact_ceiling():
    """No phantom +1 slot when cf*T*k/E divides evenly."""
    from repro.core.control_plane import capacity_for

    # 1.0 * 64 * 2 / 8 = 16 exactly -> C = 16, not 24
    assert capacity_for(64, 8, 2, 1.0) == 16
    # still a true ceiling when it doesn't divide: 1.25*100*2/8 = 31.25 -> 32
    assert capacity_for(100, 8, 2, 1.25) == 32
    # alignment floor respected
    assert capacity_for(4, 8, 1, 1.0) == 8
