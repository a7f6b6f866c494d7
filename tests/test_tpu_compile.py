"""Compile rehearsals: every Pallas kernel the served path reaches on TPU,
lowered and compiled for a described (unattached) TPU v5e at the widths of
the ``qwen3-moe-235b-a22b-1chip`` preset; ``flash_decode`` also at the dense
cell's shapes (with a guard on its program) and at other head widths, and
the dense cell's whole decode step (with a guard on its cache traffic).

Nothing runs: these compiles catch what interpret mode cannot — block shapes
that break the (8, 128) tiling rule, scoped-VMEM overflows, SMEM tables that
do not fit, primitives Mosaic does not lower.  The topology is described
inside a module-scoped fixture (never at import), so under pytest-xdist only
the worker that runs this file loads the TPU compiler, and the file skips
where no v5e topology can be described.  The persistent compilation cache is
off around the compiles: an entry written for a described chip cannot be
read back without one.
"""
from __future__ import annotations

import math
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402

CFG = get_config("qwen3-moe-235b-a22b-1chip")
B, SKV, PAGE = 16, 4096, 16          # decode slots, cache rows, KV page size
S_PREFILL = 2048                     # prompt length of one admission prefill
TREE_WORDS = (1, 3, 5, 11)           # TreePlan.from_branching([2, 1]) ancestors


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means: cannot describe here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in the program"
    return compiled


def _widths():
    return CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("T", [1, 4], ids=["chain1", "tree4"])
def test_flash_decode_compiles(one_chip, T, quant):
    from repro.kernels.flash_attention import flash_decode

    nq, nkv, hd = _widths()
    kv_dt = jnp.int8 if quant else jnp.bfloat16
    anc = jnp.asarray(TREE_WORDS, jnp.int32) if T == 4 else None

    def fn(q, k, v, idx, *scl):
        return flash_decode(
            q, k, v, idx, ancestors=anc, base=idx if anc is not None else None,
            scales=scl[0] if scl else None, interpret=False,
        )

    shapes = [((B, T, nq, hd), jnp.bfloat16), ((B, SKV, nkv, hd), kv_dt),
              ((B, SKV, nkv, hd), kv_dt), ((B,), jnp.int32)]
    if quant:
        shapes.append(((2, B, SKV), jnp.float32))
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_flash_decode_reads_cache_in_place(one_chip, quant):
    """At the dense cell's shapes (32 slots x 3,073 rows, 64 query / 8 KV
    heads of 128) the kernel takes the cache as the model stores it: no
    copy, transpose or pad of a cache-sized array in the optimised HLO."""
    from repro.kernels.flash_attention import flash_decode

    B_, S_, nq, nkv, hd = 32, 3073, 64, 8, 128
    kv_dt = jnp.int8 if quant else jnp.bfloat16

    def fn(q, k, v, idx, *scl):
        return flash_decode(q, k, v, idx, scales=scl[0] if scl else None, interpret=False)

    shapes = [((B_, 1, nq, hd), jnp.bfloat16), ((B_, S_, nkv, hd), kv_dt),
              ((B_, S_, nkv, hd), kv_dt), ((B_,), jnp.int32)]
    if quant:
        shapes.append(((2, B_, S_), jnp.float32))
    hlo = _compile(fn, one_chip, *shapes).as_text()
    moved = [
        line.strip() for line in hlo.splitlines()
        for dims in re.findall(r"= \w+\[([\d,]+)\]\S* (?:copy|transpose|pad)\(", line)
        if math.prod(int(d) for d in dims.split(",")) >= B_ * S_ * nkv * hd
    ]
    assert not moved, moved


@pytest.mark.parametrize("kv", ["", "int8"], ids=["bf16", "int8"])
def test_decode_step_keeps_the_stacked_cache_in_place(topo, monkeypatch, kv):
    """The whole spec serve step at the dense cell's widths (32 slots x
    3,073 rows, 64 / 8 heads of 128, spec width 1; 2 of its 8 layers): no
    copy, transpose, pad, dynamic-slice or dynamic-update-slice of one
    layer's cache or more in the optimised HLO (the layer scan neither
    slices the cache nor restacks it), and every cache leaf is aliased to
    its output (the donated buffer is the new cache: no whole-cache copy)."""
    import dataclasses

    import numpy as np
    from jax.sharding import AxisType, Mesh

    import repro.kernels
    from repro.configs.base import ShapeCell
    from repro.launch.steps import build_spec_serve_step

    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)  # the served path's kernels
    B_, S_, nkv, hd = 32, 3073, 8, 128
    cfg = dataclasses.replace(get_config("qwen3-32b"), num_layers=2, vocab_size=18992,
                              param_dtype="bfloat16", spec_tokens=1, kv_dtype=kv)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    step = build_spec_serve_step(cfg, mesh, ShapeCell("d", S_, B_, "decode"))
    hlo = step.jit().lower(*step.abstract_inputs).compile().as_text()
    assert "tpu_custom_call" in hlo, "no Pallas kernel in the program"
    moved = [  # cache arrays: the only ones with a 3,073-row axis (a layer's weights are larger)
        line.strip()[:200] for line in hlo.splitlines()
        for dims in re.findall(
            r"= \w+\[([\d,]+)\]\S* (?:copy|transpose|pad|dynamic-slice|dynamic-update-slice)\(",
            line)
        if S_ in (shape := [int(d) for d in dims.split(",")])
        and math.prod(shape) >= B_ * S_ * nkv * hd
    ]
    assert not moved, moved
    cache = {int(n) for n in re.findall(r"%cache\S* = \S+ parameter\((\d+)\)", hlo)}
    assert len(cache) == len(jax.tree.leaves(step.abstract_inputs[1])) == (4 if kv else 2)
    aliased = {int(n) for n in re.findall(r"\{\d+\}: \((\d+), \{\}", hlo.splitlines()[0])}
    assert cache <= aliased, (sorted(cache), sorted(aliased))


# (nq, nkv, hd): MHA, hd 96, qwen3-moe-235b-a22b per chip of four (its 4 KV
# heads split over model=4), starcoder2-3b, qwen1.5-4b, stablelm-12b
OTHER_WIDTHS = {"mha": (32, 32, 128), "hd96": (32, 8, 96), "moe_4chip_shard": (16, 1, 128),
                "starcoder2": (24, 2, 128), "qwen15": (20, 20, 128), "stablelm": (32, 8, 160)}


@pytest.mark.parametrize("widths", list(OTHER_WIDTHS.values()), ids=list(OTHER_WIDTHS))
def test_flash_decode_compiles_at_other_widths(one_chip, widths):
    from repro.kernels.flash_attention import flash_decode

    nq, nkv, hd = widths

    def fn(q, k, v, idx):
        return flash_decode(q, k, v, idx, interpret=False)

    _compile(fn, one_chip, ((8, 1, nq, hd), jnp.bfloat16), ((8, 1000, nkv, hd), jnp.bfloat16),
             ((8, 1000, nkv, hd), jnp.bfloat16), ((8,), jnp.int32))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_flash_decode_window_compiles(one_chip, quant):
    from repro.kernels.flash_attention import flash_decode_window

    nq, nkv, hd = _widths()
    W = 1024
    kv_dt = jnp.int8 if quant else jnp.bfloat16

    def fn(q, k, v, idx, *scl):
        return flash_decode_window(
            q, k, v, idx, window=W, scales=scl[0] if scl else None, interpret=False
        )

    shapes = [((B, 1, nq, hd), jnp.bfloat16), ((B, W, nkv, hd), kv_dt),
              ((B, W, nkv, hd), kv_dt), ((B,), jnp.int32)]
    if quant:
        shapes.append(((2, B, W), jnp.float32))
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("T", [1, 4], ids=["chain1", "tree4"])
def test_flash_decode_paged_compiles(one_chip, T, quant):
    from repro.kernels.flash_attention import flash_decode_paged

    nq, nkv, hd = _widths()
    max_pages = SKV // PAGE
    R = B * max_pages * PAGE
    kv_dt = jnp.int8 if quant else jnp.bfloat16
    anc = jnp.asarray(TREE_WORDS, jnp.int32) if T == 4 else None

    def fn(q, k, v, idx, pages, *scl):
        return flash_decode_paged(
            q, k, v, idx, pages, page_size=PAGE, ancestors=anc,
            base=idx if anc is not None else None,
            scales=scl[0] if scl else None, interpret=False,
        )

    shapes = [((B, T, nq, hd), jnp.bfloat16), ((R, nkv, hd), kv_dt),
              ((R, nkv, hd), kv_dt), ((B,), jnp.int32),
              ((B, max_pages), jnp.int32)]
    if quant:
        shapes.append(((2, R), jnp.float32))
    _compile(fn, one_chip, *shapes)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_moe_decode_compiles(one_chip, quant):
    from repro.core.plans import DecodePlan
    from repro.kernels.moe_decode import decode_moe

    d, f, E, k = CFG.d_model, CFG.d_ff_expert, CFG.num_experts, CFG.top_k
    T = B * 4  # a tree-width launch: B slots x 4 draft nodes
    w_dt = jnp.int8 if quant else jnp.bfloat16
    names = ("w_gate_q", "w_up_q", "w_down_q") if quant else ("w_gate", "w_up", "w_down")

    def fn(x, ids, w, wg, wu, wd, *scl):
        p = dict(zip(names, (wg, wu, wd)))
        if quant:
            p.update(w_gate_s=scl[0], w_up_s=scl[1], w_down_s=scl[2])
        return decode_moe(x, DecodePlan(ids, w), p, interpret=False)

    shapes = [((T, d), jnp.bfloat16), ((T, k), jnp.int32), ((T, k), jnp.float32),
              ((E, d, f), w_dt), ((E, d, f), w_dt), ((E, f, d), w_dt)]
    if quant:
        shapes += [((E,), jnp.float32)] * 3
    _compile(fn, one_chip, *shapes)


def test_flash_attention_prefill_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention

    nq, nkv, hd = _widths()

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    _compile(fn, one_chip, ((1, S_PREFILL, nq, hd), jnp.bfloat16),
             ((1, S_PREFILL, nkv, hd), jnp.bfloat16), ((1, S_PREFILL, nkv, hd), jnp.bfloat16))


def test_prefill_moe_data_plane_compiles(one_chip):
    """The prefill MoE layer as the served path runs it on TPU (see
    ``models.moe.moe_ffn``): routing, dispatch -> grouped-GEMM experts ->
    combine."""
    from repro.core.control_plane import capacity_for, combine, dispatch, route_topk
    from repro.kernels.grouped_gemm.ops import grouped_swiglu

    d, f, E, k = CFG.d_model, CFG.d_ff_expert, CFG.num_experts, CFG.top_k
    C = capacity_for(S_PREFILL, E, k, CFG.capacity_factor)

    def fn(x, router, wg, wu, wd):
        plan, _ = route_topk(x, router, k, C)
        y = grouped_swiglu(dispatch(x, plan), wg, wu, wd, interpret=False)
        return combine(y, plan)

    _compile(fn, one_chip, ((S_PREFILL, d), jnp.bfloat16), ((d, E), jnp.float32),
             ((E, d, f), jnp.bfloat16), ((E, d, f), jnp.bfloat16), ((E, f, d), jnp.bfloat16))
