"""MoE FFN with control-flow-plane routing (paper: Branch Divergence).

Three execution strategies map to the paper's taxonomy:

* ``dense``     — predication (von Neumann baseline): all experts run on all
                  tokens, probability-masked combine.  FLOPs x E.
* ``sync``      — switch-configuration (coupled baseline): router runs inline,
                  plan computed on the data-plane critical path.
* ``lookahead`` — Marionette: the plan arrives as an *input* (computed by the
                  control plane one stage early); this module only executes
                  dispatch -> expert GEMM -> combine on the data plane.

``experts_fn`` is injectable so the distributed runtime can substitute the
all-to-all sharded implementation (:mod:`repro.parallel.moe_parallel`) or the
Pallas grouped-GEMM kernel without touching the routing semantics.

Two data planes execute a plan:

* the reference plane — ``dispatch`` -> ``experts_fn`` -> ``combine`` (three
  HBM round-trips of the (E, C, d) slot tensors); always used when a custom
  ``experts_fn`` is injected.
  Whenever kernels run (always on TPU, see
  :func:`repro.kernels.use_kernels`) the expert compute is the grouped-GEMM
  kernel (:mod:`repro.kernels.grouped_gemm`), differentiable through an
  einsum backward so MoE training runs it too.
* the fused plane (``fused=True``) — the plan's flat SMEM control words
  steer gather -> grouped GEMM -> scatter inside two Pallas launches
  (:mod:`repro.kernels.moe_fused`); no (E, C, d) tensor ever hits HBM.  It
  runs in interpret mode only (CPU parity tests and
  ``benchmarks/moe_fused.py``'s HLO counts): the TPU compiler refuses its
  per-row gather and scatter, so on TPU ``fused=True`` raises.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.control_plane import (
    RouterAux,
    capacity_for,
    combine,
    dense_moe_predication,
    dispatch,
    route_topk,
)
from repro.core.plans import DecodePlan, DispatchPlan
from repro.models.layers import dense_init, swiglu_tokens

Params = Dict[str, Any]

# experts_fn(x_slots (E, C, d), expert_params) -> y_slots (E, C, d)
ExpertsFn = Callable[[jnp.ndarray, Params], jnp.ndarray]

def init_moe(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d = cfg.d_model
    dff = cfg.d_ff_expert or cfg.d_ff
    E = cfg.num_experts
    ks = jax.random.split(key, 5)
    down_scale = 1.0 / math.sqrt(dff * 2 * cfg.num_layers)
    p: Params = {
        "router": dense_init(ks[0], d, E, scale=0.02, dtype=jnp.float32),  # control plane: f32
        "w_gate": (jax.random.normal(ks[1], (E, d, dff)) / math.sqrt(d)).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (E, d, dff)) / math.sqrt(d)).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (E, dff, d)) * down_scale).astype(dtype),
    }
    if cfg.num_shared_experts:
        kg, ku, kd = jax.random.split(ks[4], 3)
        sh = cfg.num_shared_experts
        p["shared"] = {
            "w_gate": (jax.random.normal(kg, (d, sh * dff)) / math.sqrt(d)).astype(dtype),
            "w_up": (jax.random.normal(ku, (d, sh * dff)) / math.sqrt(d)).astype(dtype),
            "w_down": (jax.random.normal(kd, (sh * dff, d)) * down_scale).astype(dtype),
        }
    if cfg.expert_dtype == "int8":
        p.update(quantize_expert_stacks(p))
    return p


def quantize_expert_stacks(p: Params) -> Params:
    """Pre-quantize the routed expert stacks for the decode data plane.

    Returns int8 twins (``w_gate_q`` et al.) plus per-expert f32 scale
    vectors (``w_gate_s``: (E,)) — the scale control words the decode kernel
    reads from SMEM next to the plan's expert ids.  The f32 stacks stay in
    the param dict untouched: prefill and training never see int8, only the
    plan-steered decode launch does (see kernels/moe_decode/ops.decode_moe).
    """
    from repro.core.quant import quantize_int8

    out: Params = {}
    for name in ("w_gate", "w_up", "w_down"):
        q, s = quantize_int8(p[name].astype(jnp.float32), axis=(1, 2))
        out[name + "_q"] = q
        out[name + "_s"] = s[:, 0, 0].astype(jnp.float32)  # (E,)
    return out


def _shared_experts(xf: jnp.ndarray, p: Params) -> jnp.ndarray:
    """Always-on shared-expert SwiGLU over flat tokens (T, d) -> (T, d)."""
    sh = p["shared"]
    g = xf @ sh["w_gate"].astype(xf.dtype)
    u = xf @ sh["w_up"].astype(xf.dtype)
    return (jax.nn.silu(g) * u) @ sh["w_down"].astype(xf.dtype)


def local_experts_fn(x_slots: jnp.ndarray, p: Params) -> jnp.ndarray:
    """Default data-plane expert compute: batched per-expert SwiGLU GEMMs."""
    g = jnp.einsum("ecd,edf->ecf", x_slots, p["w_gate"].astype(x_slots.dtype))
    u = jnp.einsum("ecd,edf->ecf", x_slots, p["w_up"].astype(x_slots.dtype))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"].astype(x_slots.dtype))


def default_experts_fn(cfg: ModelConfig) -> ExpertsFn:
    """The expert compute of the dispatch->experts->combine plane: the
    grouped-GEMM kernel whenever kernels run, batched einsums otherwise."""
    from repro.kernels import use_kernels

    if use_kernels(cfg):
        from repro.kernels.grouped_gemm.ops import pallas_experts_fn

        return pallas_experts_fn
    return local_experts_fn


def moe_ffn(
    x: jnp.ndarray,  # (B, S, d)
    p: Params,
    cfg: ModelConfig,
    *,
    plan: Optional[DispatchPlan] = None,
    experts_fn: Optional[ExpertsFn] = None,
    capacity: Optional[int] = None,
    fused: Optional[bool] = None,
) -> Tuple[jnp.ndarray, RouterAux]:
    """Apply the MoE FFN.  If ``plan`` is provided (lookahead mode) the router
    is NOT run here — the control plane already produced the configuration.

    ``fused=True`` selects the fused Pallas gather->GEMM->scatter pipeline;
    otherwise the dispatch->experts_fn->combine composition runs, with the
    grouped-GEMM kernel as the default ``experts_fn`` whenever kernels run.
    """
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    T = B * S

    if cfg.route_mode == "dense" and plan is None:
        logits = jnp.asarray(xf, jnp.float32) @ p["router"]
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, cfg.top_k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        # mask to top-k then dense predication over all experts
        mask = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], top_e].set(top_w)

        def one_expert(pe, xt):
            return swiglu_tokens(xt, pe["w_gate"], pe["w_up"], pe["w_down"])

        expert_params = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
        y = dense_moe_predication(xf, mask, one_expert, expert_params)
        aux = RouterAux(
            load_balance_loss=jnp.float32(0.0),
            router_z_loss=jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
            fraction_dropped=jnp.float32(0.0),
        )
    else:
        if plan is None:  # sync mode: route inline (coupled control flow)
            C = capacity if capacity is not None else capacity_for(T, cfg.num_experts, cfg.top_k, cfg.capacity_factor)
            plan, aux = route_topk(xf, p["router"], cfg.top_k, C)
        else:
            aux = RouterAux(jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0))
        if fused and experts_fn is not None:
            raise ValueError(
                "fused=True replaces the dispatch->experts_fn->combine "
                "composition entirely; a custom experts_fn cannot apply. "
                "Pass fused=False (or drop experts_fn)."
            )
        if fused:
            from repro.kernels import on_tpu
            from repro.kernels.moe_fused.ops import fused_moe_fn

            if on_tpu():
                raise NotImplementedError(
                    "fused=True runs only in the Pallas interpreter: the TPU "
                    "compiler refuses the fused kernels' per-row gather and "
                    "scatter. Serve on TPU with fused=False (dispatch -> "
                    "grouped GEMM -> combine)."
                )

            y = fused_moe_fn(xf, plan, p).astype(x.dtype)
        else:
            x_slots = dispatch(xf, plan)  # (E, C, d)
            y_slots = (experts_fn or default_experts_fn(cfg))(x_slots, p)
            y = combine(y_slots, plan).astype(x.dtype)

    if "shared" in p:
        y = y + _shared_experts(xf, p)
    return y.reshape(B, S, d), aux


def moe_decode_ffn(
    x: jnp.ndarray,  # (B, 1, d) decode-step FFN input
    plan: DecodePlan,
    p: Params,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Execute a cache-carried DecodePlan on the tiny-T decode data plane.

    The router does NOT run here — the plan was computed one step earlier
    (temporally loosely-coupled control) and arrives as a cache read.  The
    data plane is one plan-steered launch (:mod:`repro.kernels.moe_decode`):
    no capacity sort, no (E, C, d) slot tensors.
    """
    from repro.kernels.moe_decode import decode_moe

    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    y = decode_moe(xf, plan.flatten(), p, interpret=interpret)
    if "shared" in p:
        y = y + _shared_experts(xf, p)
    return y.reshape(B, S, d)


def router_logits(x: jnp.ndarray, p: Params) -> jnp.ndarray:
    """Control-plane helper: raw router logits for (..., d) hidden states."""
    return jnp.asarray(x, jnp.float32) @ p["router"]


def moe_layer(
    x_ffn: jnp.ndarray,  # (B, S, d) normalized FFN input (data plane)
    route_src: Optional[jnp.ndarray],  # (B, S, d) control-plane routing source
    p: Params,
    cfg: ModelConfig,
    *,
    experts_fn: Optional[ExpertsFn] = None,
    capacity: Optional[int] = None,
    fused: Optional[bool] = None,
) -> Tuple[jnp.ndarray, RouterAux]:
    """Mode-dispatching MoE layer.

    lookahead: the plan is computed from ``route_src`` (the previous layer's
    residual stream — available before this layer's attention finishes), so
    the control plane (router matmul + sort + plan build) is independent of
    the current layer's data plane and overlaps with it.  sync: the plan is
    computed from ``x_ffn`` itself — serialized (coupled) control flow.
    dense: predication baseline.
    """
    B, S, d = x_ffn.shape
    T = B * S
    if cfg.route_mode == "dense":
        return moe_ffn(x_ffn, p, cfg)
    C = capacity if capacity is not None else capacity_for(T, cfg.num_experts, cfg.top_k, cfg.capacity_factor)
    src = x_ffn if (cfg.route_mode == "sync" or route_src is None) else route_src
    plan, aux = route_topk(src.reshape(T, d), p["router"], cfg.top_k, C)
    y, _ = moe_ffn(x_ffn, p, cfg, plan=plan, experts_fn=experts_fn, fused=fused)
    return y, aux
