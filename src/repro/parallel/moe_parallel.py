"""Expert-parallel MoE under shard_map: the pod-scale data plane driven by the
control-flow plane's dispatch plans.

Marionette mapping: the *control plane* (router matmul -> top-k -> plan: a few
KB of int32/f32 per shard) runs decoupled from the *data plane* (expert GEMMs
and bulk-activation all_to_alls).  In ``lookahead`` mode the plan source is
the previous layer's residual stream, so the control computation overlaps the
current layer's attention on the data plane (Proactive PE Configuration); the
all_to_all "configures" the peer shards' expert slots peer-to-peer, with no
host/CCU round trip (autonomous, peer-to-peer control).

Two data-plane strategies (selected by token count, like the Control Flow
Sender's operator modes):

* ``a2a``  (train/prefill): tokens are additionally split along the model
  axis (sequence parallelism); each shard routes its T/ep tokens, dispatches
  into fixed-capacity slots (E, C, d), and ONE tiled all_to_all re-buckets
  slots so each shard holds (E/ep, ep*C, d) for its local experts.  Reverse
  a2a + local combine + all_gather restores (B, S, d).
* ``psum`` (decode): token counts are tiny; every model shard routes the same
  tokens, computes only its local expert slice, and partial outputs are
  summed with one psum (cheaper than a2a at decode batch sizes).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from repro.configs.base import ModelConfig
from repro.core.control_plane import capacity_for, combine, dispatch, route_topk
from repro.core.plans import DecodePlan
from repro.models.moe import _shared_experts, default_experts_fn

Params = Dict[str, Any]


def _moe_param_specs(p_example: Params) -> Params:
    """in_specs pytree for the MoE param dict: experts over model, rest replicated."""
    specs: Params = {}
    for k in p_example:
        if k in ("w_gate", "w_up", "w_down", "w_gate_q", "w_up_q", "w_down_q"):
            specs[k] = P("model", None, None)
        elif k in ("w_gate_s", "w_up_s", "w_down_s"):
            specs[k] = P("model")  # (E,) scale words ride the expert axis
        elif k == "shared":
            specs[k] = {kk: P() for kk in p_example[k]}
        else:
            specs[k] = P()
    return specs


def make_sharded_moe_apply(
    cfg: ModelConfig,
    mesh: Mesh,
    batch_axes: Tuple[str, ...],
    *,
    ep_axis: str = "model",
    experts_fn=None,
    capacity_factor: Optional[float] = None,
):
    """Build the distributed MoeApply (x_ffn, route_src, params) -> (y, aux(2,)).

    ``batch_axes`` shard the leading batch dim of x (may be empty for B=1).
    The local expert compute is ``experts_fn``, by default the grouped-GEMM
    kernel whenever kernels run (:func:`repro.models.moe.default_experts_fn`).
    """
    E, k = cfg.num_experts, cfg.top_k
    ep = mesh.shape[ep_axis]
    assert E % ep == 0, f"{E} experts not divisible by ep={ep}"
    E_loc = E // ep
    cf = capacity_factor if capacity_factor is not None else cfg.capacity_factor
    x_spec = P(batch_axes if batch_axes else None, None, None)
    all_axes = tuple(batch_axes) + (ep_axis,)
    experts_fn = experts_fn or default_experts_fn(cfg)

    # ------------------------------------------------------------------
    # strategy a2a: sequence-split + all_to_all (train / prefill)
    # ------------------------------------------------------------------
    def _a2a_body(x, rs, p):
        B_loc, S, d = x.shape
        Sc = S // ep
        midx = jax.lax.axis_index(ep_axis)
        xs = jax.lax.dynamic_slice_in_dim(x, midx * Sc, Sc, axis=1)
        rss = jax.lax.dynamic_slice_in_dim(rs, midx * Sc, Sc, axis=1)
        T_loc = B_loc * Sc
        C = capacity_for(T_loc, E, k, cf)

        # -- control plane: plan for this shard's tokens (tiny tensors) ----
        plan, aux = route_topk(rss.reshape(T_loc, d), p["router"], k, C)

        # -- data plane: dispatch -> a2a -> experts -> a2a -> combine ------
        slots = dispatch(xs.reshape(T_loc, d), plan)  # (E, C, d)
        slots = jax.lax.all_to_all(
            slots, ep_axis, split_axis=0, concat_axis=1, tiled=True
        )  # (E_loc, ep*C, d)
        y_slots = experts_fn(slots, p)  # local experts, (E_loc, ep*C, d)
        y_slots = jax.lax.all_to_all(
            y_slots, ep_axis, split_axis=1, concat_axis=0, tiled=True
        )  # (E, C, d)
        y = combine(y_slots, plan).astype(x.dtype)  # (T_loc, d)

        if "shared" in p:  # shared experts: replicated weights, local tokens
            sh = p["shared"]
            xf = xs.reshape(T_loc, d)
            g = xf @ sh["w_gate"].astype(x.dtype)
            u = xf @ sh["w_up"].astype(x.dtype)
            y = y + (jax.nn.silu(g) * u) @ sh["w_down"].astype(x.dtype)

        y = y.reshape(B_loc, Sc, d)
        y = jax.lax.all_gather(y, ep_axis, axis=1, tiled=True)  # (B_loc, S, d)
        aux_v = jnp.stack([aux.load_balance_loss, aux.router_z_loss])
        aux_v = jax.lax.pmean(aux_v, all_axes)
        return y, aux_v

    # ------------------------------------------------------------------
    # strategy psum: replicated routing + expert-sliced compute (decode)
    # ------------------------------------------------------------------
    def _psum_body(x, rs, p):
        B_loc, S, d = x.shape
        T_loc = B_loc * S
        C = capacity_for(T_loc, E, k, cf)
        midx = jax.lax.axis_index(ep_axis)

        plan, aux = route_topk(rs.reshape(T_loc, d), p["router"], k, C)
        slots = dispatch(x.reshape(T_loc, d), plan)  # (E, C, d) replicated
        slots_loc = jax.lax.dynamic_slice_in_dim(slots, midx * E_loc, E_loc, axis=0)
        y_loc = experts_fn(slots_loc, p)  # (E_loc, C, d)

        # combine only assignments owned by this shard, sum across shards
        base = midx * E_loc * C
        idx = plan.combine_idx - base
        local = (idx >= 0) & (idx < E_loc * C)
        shifted = plan.replace_combine(
            combine_idx=jnp.where(local, idx, -1),
            combine_w=jnp.where(local, plan.combine_w, 0.0),
        )
        y = combine(y_loc, shifted)
        y = jax.lax.psum(y, ep_axis).astype(x.dtype)

        if "shared" in p:
            sh = p["shared"]
            xf = x.reshape(T_loc, d)
            g = xf @ sh["w_gate"].astype(x.dtype)
            u = xf @ sh["w_up"].astype(x.dtype)
            y = y + (jax.nn.silu(g) * u) @ sh["w_down"].astype(x.dtype)

        aux_v = jnp.stack([aux.load_balance_loss, aux.router_z_loss])
        aux_v = jax.lax.pmean(aux_v, tuple(batch_axes)) if batch_axes else aux_v
        return y.reshape(B_loc, S, d), aux_v

    def moe_apply(x_ffn: jnp.ndarray, route_src: Optional[jnp.ndarray], p: Params):
        rs = x_ffn if (route_src is None or cfg.route_mode != "lookahead") else route_src
        S = x_ffn.shape[1]
        body = _a2a_body if S % ep == 0 and S >= ep else _psum_body
        specs_p = _moe_param_specs(p)
        fn = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(x_spec, x_spec, specs_p),
            out_specs=(x_spec, P()),
            check_vma=False,
        )
        return fn(x_ffn, rs, p)

    return moe_apply


def make_sharded_decode_apply(
    cfg: ModelConfig,
    mesh: Mesh,
    batch_axes: Tuple[str, ...],
    *,
    ep_axis: str = "model",
):
    """Distributed Agile decode plane: execute a cache-carried DecodePlan with
    the psum strategy, driven by per-shard plan slices.

    Returns ``decode_apply(x_ffn (B, S, d), plan, p) -> y (B, S, d)`` — the
    decode-plane dual of :func:`make_sharded_moe_apply`'s psum body.  The
    router does NOT run here: the plan was computed one step earlier and
    arrives as a cache read, replicated over the model axis (control is tiny;
    replicating it is the peer-to-peer delivery).  Each shard filters the
    plan rows against its resident expert slice
    (:meth:`~repro.core.plans.DecodePlan.shard_slice` — a mask on expert ids,
    no slot arithmetic), runs the capacity-free decode data plane over its
    local (E/ep, d, f) weight stacks only, and ONE psum combines the partial
    outputs.  The spec-width plan vector ((B, T, k) fields, one row per draft
    position) flattens to the same (B*T, k) control layout the single-host
    kernel consumes, so speculative verify/rollback semantics are preserved
    under shard_map unchanged.
    """
    E, k = cfg.num_experts, cfg.top_k
    ep = mesh.shape[ep_axis]
    if E % ep:
        raise ValueError(
            f"distributed decode plane: {E} experts are not divisible by the "
            f"'{ep_axis}' mesh axis ({ep}); pick a model-parallel degree that "
            f"divides num_experts (or 1)"
        )
    E_loc = E // ep
    x_spec = P(batch_axes if batch_axes else None, None, None)

    def _body(x, pe, pw, p):
        from repro.kernels.moe_decode import decode_moe

        B_loc, S, d = x.shape
        T_loc = B_loc * S
        midx = jax.lax.axis_index(ep_axis)
        plan = DecodePlan(pe.reshape(T_loc, k), pw.reshape(T_loc, k))
        xf = x.reshape(T_loc, d)
        y = decode_moe(xf, plan.shard_slice(midx * E_loc, E_loc), p)
        y = jax.lax.psum(y, ep_axis).astype(x.dtype)
        if "shared" in p:  # shared experts: replicated weights, added post-psum
            y = y + _shared_experts(xf, p)
        return y.reshape(B_loc, S, d)

    def decode_apply(x_ffn: jnp.ndarray, plan: DecodePlan, p: Params) -> jnp.ndarray:
        B, S, _ = x_ffn.shape
        # plan fields arrive (B, k) at spec width 1 or (B, T, k) as a draft
        # vector; normalize to (B, S, k) so the batch axes shard with x
        pe = plan.expert_ids.reshape(B, S, k)
        pw = plan.weights.reshape(B, S, k)
        specs_p = _moe_param_specs(p)
        fn = jax.shard_map(
            _body,
            mesh=mesh,
            in_specs=(x_spec, x_spec, x_spec, specs_p),
            out_specs=x_spec,
            check_vma=False,
        )
        return fn(x_ffn, pe, pw, p)

    return decode_apply
