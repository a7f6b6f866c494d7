"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret=True on CPU;
spec deliverable c): shapes x dtypes per kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.control_plane import capacity_for, route_topk

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# moe_dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("T,d,E,k", [(32, 128, 4, 1), (64, 256, 8, 2), (96, 128, 16, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_dispatch_sweep(T, d, E, k, dtype):
    from repro.kernels.moe_dispatch import ops, ref

    rng = np.random.default_rng(T + E)
    x = jnp.asarray(rng.standard_normal((T, d)), dtype)
    wr = jnp.asarray(rng.standard_normal((d, E)) * 0.1, jnp.float32)
    plan, _ = route_topk(x.astype(jnp.float32), wr, k, capacity_for(T, E, k, 1.25))

    np.testing.assert_allclose(
        np.asarray(ops.dispatch(x, plan), np.float32),
        np.asarray(ref.dispatch(x, plan), np.float32),
        rtol=0, atol=0,
    )
    y_slots = ref.dispatch(x, plan) * jnp.asarray(1.5, dtype)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(ops.combine(y_slots, plan), np.float32),
        np.asarray(ref.combine(y_slots, plan), np.float32),
        rtol=tol, atol=tol,
    )


# ---------------------------------------------------------------------------
# grouped_gemm
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("E,M,K,N", [(2, 64, 64, 64), (4, 100, 96, 72), (8, 128, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_gemm_sweep(E, M, K, N, dtype):
    from repro.kernels.grouped_gemm import ops, ref

    rng = np.random.default_rng(E * M)
    x = jnp.asarray(rng.standard_normal((E, M, K)), dtype)
    w = jnp.asarray(rng.standard_normal((E, K, N)), dtype)
    got = ops.grouped_gemm(x, w)
    want = ref.grouped_gemm(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol * K
    )


def test_grouped_swiglu_matches_local_experts_fn():
    from repro.kernels.grouped_gemm import ops
    from repro.models.moe import local_experts_fn

    rng = np.random.default_rng(0)
    E, C, d, f = 4, 32, 64, 128
    x = jnp.asarray(rng.standard_normal((E, C, d)), jnp.float32)
    p = {
        "w_gate": jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32),
        "w_up": jnp.asarray(rng.standard_normal((E, d, f)) * 0.1, jnp.float32),
        "w_down": jnp.asarray(rng.standard_normal((E, f, d)) * 0.1, jnp.float32),
    }
    got = ops.pallas_experts_fn(x, p)
    want = local_experts_fn(x, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_moe_ffn_grad_through_grouped_gemm_kernel():
    """Whenever kernels run (always on TPU) the MoE layer's experts are the
    grouped-GEMM kernel, and training differentiates through it: the
    gradient must equal the einsum data plane's."""
    import dataclasses

    from repro.configs import get_smoke_config
    from repro.models import moe as moe_mod

    cfg = dataclasses.replace(get_smoke_config("qwen3-moe-235b-a22b"), route_mode="sync")
    p = moe_mod.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))

    def loss(p, x, c, experts_fn=None):
        y, _ = moe_mod.moe_ffn(x, p, c, experts_fn=experts_fn)
        return jnp.sum(jnp.sin(y))

    kern = dataclasses.replace(cfg, use_pallas=True)
    assert moe_mod.default_experts_fn(kern) is not moe_mod.local_experts_fn
    got = jax.grad(loss, argnums=(0, 1))(p, x, kern)
    want = jax.grad(loss, argnums=(0, 1))(p, x, cfg, moe_mod.local_experts_fn)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "B,S,nq,nkv,hd,window",
    [
        (2, 256, 4, 2, 64, 0),
        (1, 200, 8, 8, 128, 0),   # seq padding path
        (2, 256, 4, 1, 64, 96),   # MQA + local window
        (1, 384, 6, 2, 96, 128),  # GQA ratio 3, non-pow2 head_dim
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, nq, nkv, hd, window, dtype):
    from repro.kernels.flash_attention import ops, ref

    rng = np.random.default_rng(S + nq)
    q = jnp.asarray(rng.standard_normal((B, S, nq, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, nkv, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, nkv, hd)), dtype)
    got = ops.flash_attention(q, k, v, causal=True, window=window)
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


# ---------------------------------------------------------------------------
# rglru_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,T,W", [(2, 100, 48), (1, 256, 512), (3, 64, 130)])
@pytest.mark.parametrize("with_h0", [False, True])
def test_rglru_scan_sweep(B, T, W, with_h0):
    from repro.kernels.rglru_scan import ops, ref

    rng = np.random.default_rng(T + W)
    a = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((B, T, W)), jnp.float32))
    b = jnp.asarray(rng.standard_normal((B, T, W)) * 0.1, jnp.float32)
    h0 = jnp.asarray(rng.standard_normal((B, W)) * 0.1, jnp.float32) if with_h0 else None
    got = ops.rglru_scan(a, b, h0)
    want = ref.rglru_scan(a, b, h0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ssd_scan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,T,H,P,N,Q", [(2, 96, 3, 16, 24, 32), (1, 256, 4, 64, 128, 128), (2, 100, 2, 32, 64, 64)])
def test_ssd_scan_sweep(B, T, H, P, N, Q):
    from repro.kernels.ssd_scan import ops, ref

    rng = np.random.default_rng(T + N)
    x = jnp.asarray(rng.standard_normal((B, T, H, P)) * 0.5, jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((B, T, H)), jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.standard_normal((H,)) * 0.3, jnp.float32))
    bm = jnp.asarray(rng.standard_normal((B, T, N)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.standard_normal((B, T, N)) * 0.3, jnp.float32)
    yk, hk = ops.ssd_scan(x, dt, a, bm, cm, chunk=Q)
    yr, hr = ref.ssd_scan(x, dt, a, bm, cm, Q)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(hk), np.asarray(hr), rtol=2e-5, atol=2e-5)


def test_ssd_scan_unpadded_tail():
    """T not a multiple of the chunk exercises the padding path; the padded
    region must not perturb the final state."""
    from repro.kernels.ssd_scan import ops, ref

    rng = np.random.default_rng(7)
    B, T, H, P, N, Q = 1, 70, 2, 8, 16, 32
    x = jnp.asarray(rng.standard_normal((B, T, H, P)) * 0.5, jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((B, T, H)), jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.standard_normal((H,)) * 0.3, jnp.float32))
    bm = jnp.asarray(rng.standard_normal((B, T, N)) * 0.3, jnp.float32)
    cm = jnp.asarray(rng.standard_normal((B, T, N)) * 0.3, jnp.float32)
    yk, hk = ops.ssd_scan(x, dt, a, bm, cm, chunk=Q)
    yr, hr = ref.ssd_scan(x, dt, a, bm, cm, Q)
    np.testing.assert_allclose(np.asarray(yk), np.asarray(yr), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash-decode kernels vs their f32 oracles (flash_attention/ref.py)
# ---------------------------------------------------------------------------

TREE4 = (1, 3, 5, 11)  # TreePlan.from_branching([2, 1]) ancestor words
# flash_decode reads the cache in place at other widths, (nq, nkv, hd): the
# dense cell's GQA 8 x 128, MHA (group 1), hd 96. S = 37 leaves a ragged last
# block of 8 rows, and every cache row past each slot's last token holds NaN
RAGGED = {"ragged_gqa": (64, 8, 128), "ragged_tree": (64, 8, 128),
          "ragged_mha": (4, 4, 16), "ragged_hd96": (8, 2, 96)}


def _kv(rng, shape, quant):
    from repro.core.quant import quantize_int8

    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    if not quant:
        return x, None
    q, s = quantize_int8(x, axis=(-2, -1))
    return q, s[..., 0, 0]


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("kind", ["chain", "tree", "window", "paged", "paged_tree", *RAGGED])
def test_flash_decode_kernels_match_oracle(kind, quant):
    from repro.kernels.flash_attention import decode as kern
    from repro.kernels.flash_attention import ref

    rng = np.random.default_rng(len(kind) + quant)
    B, nq, nkv, hd, S, ps = 2, 4, 2, 16, 32, 8
    if kind in RAGGED:
        (nq, nkv, hd), S = RAGGED[kind], 37
    T = 4 if "tree" in kind else 2
    q = jnp.asarray(rng.standard_normal((B, T, nq, hd)), jnp.float32)
    (k, ks), (v, vs) = _kv(rng, (B, S, nkv, hd), quant), _kv(rng, (B, S, nkv, hd), quant)
    scl = None if ks is None else jnp.stack([ks, vs])
    idx = jnp.asarray([5, S - T], jnp.int32)
    tree = dict(ancestors=jnp.asarray(TREE4, jnp.int32), base=idx) if "tree" in kind else {}
    if kind == "window":
        got = kern.flash_decode_window(q, k, v, idx + 40, window=12, scales=scl, bkv=8, interpret=True)
        want = ref.flash_decode_window(q, k, v, idx + 40, window=12, scales=scl)
    elif kind.startswith("paged"):
        # slot b's logical page i lives at a shuffled physical page
        mp = S // ps
        perm = jnp.asarray(rng.permutation(B * mp), jnp.int32).reshape(B, mp)
        pool = lambda c: jnp.zeros((B * mp * ps,) + c.shape[2:], c.dtype).at[  # noqa: E731
            (perm[:, :, None] * ps + jnp.arange(ps)).reshape(B, S)].set(c)
        pk, pv = pool(k), pool(v)
        pscl = None if scl is None else jnp.stack([pool(ks), pool(vs)])
        got = kern.flash_decode_paged(q, pk, pv, idx, perm, page_size=ps, scales=pscl,
                                      interpret=True, **tree)
        want = ref.flash_decode_paged(q, pk, pv, idx, perm, page_size=ps, scales=pscl, **tree)
    else:
        kn, vn, sn = k, v, scl
        if kind in RAGGED:  # poison what no token may see: the kernel must not read it
            dead = jnp.arange(S)[None, :] >= (idx + T)[:, None]  # (B, S)
            if quant:
                sn = jnp.where(dead, jnp.nan, scl)
            else:
                kn, vn = (jnp.where(dead[:, :, None, None], jnp.nan, c) for c in (k, v))
        got = kern.flash_decode(q, kn, vn, idx, scales=sn, bkv=8, interpret=True, **tree)
        want = ref.flash_decode(q, k, v, idx, scales=scl, **tree)
        assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


# the decode scan's stacked cache: (T, ragged lengths, int8, tree)
STACKED = {"chain1": (1, False, False), "chain4": (4, False, False),
           "ragged": (2, False, False), "int8": (2, True, False), "tree": (4, False, True)}


@pytest.mark.parametrize("case", list(STACKED))
def test_flash_decode_stacked_layer_bitwise(case):
    """flash_decode on the layer-stacked (L, B, S, nkv, hd) cache with
    ``layer=i`` equals the call on ``cache[i]`` bit for bit, and reads no
    other layer (they hold NaN)."""
    from repro.kernels.flash_attention import flash_decode

    T, quant, tree = STACKED[case]
    rng = np.random.default_rng(len(case))
    Ln, i, B, nq, nkv, hd, S = 3, 1, 3, 8, 2, 16, 37
    q = jnp.asarray(rng.standard_normal((B, T, nq, hd)), jnp.float32)
    (k, ks), (v, vs) = (_kv(rng, (Ln, B, S, nkv, hd), quant) for _ in range(2))
    idx = jnp.asarray([0, 13, S - T] if case == "ragged" else [9, 9, 9], jnp.int32)
    kw = dict(ancestors=jnp.asarray(TREE4, jnp.int32), base=idx) if tree else {}
    scl = None if ks is None else jnp.stack([ks, vs])  # (2, L, B, S)
    want = flash_decode(q, k[i], v[i], idx, scales=None if scl is None else scl[:, i],
                        bkv=8, interpret=True, **kw)
    other = jnp.arange(Ln)[:, None, None] != i  # (L, 1, 1): poison every other layer
    if quant:
        scl = jnp.where(other[None], jnp.nan, scl)
    else:
        k, v = (jnp.where(other[..., None, None], jnp.nan, c) for c in (k, v))
    got = flash_decode(q, k, v, idx, scales=scl, layer=jnp.int32(i), bkv=8,
                       interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_flash_decode_layer_needs_stacked_cache():
    from repro.kernels.flash_attention import flash_decode

    q, c = jnp.zeros((1, 1, 2, 8)), jnp.zeros((1, 8, 1, 8))
    with pytest.raises(ValueError, match="layer"):
        flash_decode(q, c, c, 0, layer=0, interpret=True)
    with pytest.raises(ValueError, match="layer"):
        flash_decode(q, c[None], c[None], 0, interpret=True)
