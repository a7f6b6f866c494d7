"""Step builders: assemble the jitted train / prefill / serve steps with full
sharding specifications for a (config, mesh, shape-cell) triple.

These are shared by the dry-run (lower/compile against ShapeDtypeStructs) and
the real drivers (train.py / serve.py) — the dry-run compiles EXACTLY what
the drivers run.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCell
from repro.models import transformer as T
from repro.models.model import Model
from repro.optim import clip_by_global_norm, cosine_schedule, make_optimizer
from repro.parallel.moe_parallel import make_sharded_decode_apply, make_sharded_moe_apply
from repro.parallel.sharding import (
    batch_spec,
    cache_shardings,
    data_axes,
    param_pspecs,
    param_shardings,
)

Params = Dict[str, Any]


@dataclasses.dataclass
class StepBundle:
    """A fully-specified step: fn + in/out shardings + abstract inputs."""

    name: str
    fn: Callable
    in_shardings: Tuple
    out_shardings: Any
    abstract_inputs: Tuple
    donate_argnums: Tuple[int, ...]
    model: Model

    def jit(self):
        return jax.jit(
            self.fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )

    def lower(self):
        return self.jit().lower(*self.abstract_inputs)


def _sds(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def _abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        lambda k: T.init_params(k, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32)
    )


def init_sharded_params(cfg: ModelConfig, mesh: Mesh, key) -> Any:
    """Random (seeded) params created directly under their serve shardings,
    so no device ever holds more than its own share."""
    shard = param_shardings(_abstract_params(cfg), mesh)
    return jax.jit(lambda k: T.init_params(k, cfg), out_shardings=shard)(key)


def build_model(cfg: ModelConfig, mesh: Mesh, batch: int, *, strategy: str = "tp") -> Model:
    """Model with the distributed MoE apply + residual constraint bound to
    this mesh/batch."""
    baxes = batch_spec(batch, mesh)[0] or ()
    baxes = (baxes,) if isinstance(baxes, str) else tuple(baxes)
    moe_apply = None
    decode_apply = None
    if cfg.is_moe:
        raw = make_sharded_moe_apply(cfg, mesh, baxes)

        def moe_apply(x, rs, p):
            y, aux = raw(x, rs, p)
            return y, aux

        if cfg.decode_plane:
            # distributed decode plane: cache-carried DecodePlans execute as
            # per-shard slices + one psum instead of the replicated fallback
            # (raises, not falls back, when experts don't divide the mesh)
            decode_apply = make_sharded_decode_apply(cfg, mesh, baxes)

    res_spec = P(baxes or None, None, None)
    if strategy == "fsdp":
        axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        if batch % total == 0:
            res_spec = P(axes, None, None)

    def constrain(x):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, res_spec))

    return Model(cfg, moe_apply=moe_apply, constrain=constrain, decode_moe_apply=decode_apply)


def opt_state_pspecs(opt_state_abs: Any, params_abs: Any, mesh: Mesh, *, strategy: str = "tp") -> Any:
    """Shardings for optimizer state, derived from the param shardings.

    adamw: state mirrors params ({"m": tree, "v": tree}).
    adafactor: dict leaves {v} | {vr, vc} with reduced shapes — keep the
    model-sharded axis when it survives the factoring, else replicate.
    """
    pspecs = param_pspecs(params_abs, mesh, strategy=strategy)

    if isinstance(opt_state_abs, dict) and set(opt_state_abs) <= {"m", "v", "count"}:
        return {k: jax.tree.map(lambda s: s, pspecs) for k in opt_state_abs}

    # adafactor: params tree with dict leaves
    flat_p, treedef = jax.tree.flatten(params_abs)
    flat_spec = treedef.flatten_up_to(pspecs)
    flat_state = treedef.flatten_up_to(opt_state_abs)

    def reduce_spec(spec: P, pshape, sshape) -> P:
        if tuple(sshape) == tuple(pshape):
            return spec
        entries = list(spec) + [None] * (len(pshape) - len(spec))
        if len(sshape) == len(pshape) - 1 and tuple(sshape) == tuple(pshape[:-1]):
            return P(*entries[:-1])  # vr: row stats (last axis reduced)
        if len(sshape) == len(pshape) - 1 and tuple(sshape) == tuple(pshape[:-2] + pshape[-1:]):
            return P(*(entries[:-2] + entries[-1:]))  # vc: col stats
        return P()

    out = []
    for p, spec, st in zip(flat_p, flat_spec, flat_state):
        out.append({k: reduce_spec(spec, p.shape, v.shape) for k, v in st.items()})
    return treedef.unflatten(out)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def build_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    cell: ShapeCell,
    *,
    lr: float = 3e-4,
    total_steps: int = 10_000,
    grad_clip: float = 1.0,
    strategy: str = "tp",
) -> StepBundle:
    B, S = cell.global_batch, cell.seq_len
    model = build_model(cfg, mesh, B, strategy=strategy)
    optimizer = make_optimizer(cfg.optimizer, cosine_schedule(lr, 100, total_steps))

    def train_step(params, opt_state, step, tokens, frontend=None):
        def loss_fn(p):
            loss, metrics = model.forward_train(p, tokens, frontend)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        grads, gnorm = clip_by_global_norm(grads, grad_clip)
        updates, opt_state = optimizer.update(grads, opt_state, params, step)
        params = jax.tree.map(lambda p, u: (p + u).astype(p.dtype), params, updates)
        metrics = dict(metrics, grad_norm=gnorm)
        return params, opt_state, step + 1, metrics

    params_abs = _abstract_params(cfg)
    opt_abs = jax.eval_shape(optimizer.init, params_abs)
    p_shard = param_shardings(params_abs, mesh, strategy=strategy)
    o_pspec = opt_state_pspecs(opt_abs, params_abs, mesh, strategy=strategy)
    o_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), o_pspec)
    bspec = batch_spec(B, mesh, extra_dims=1)
    if strategy == "fsdp":
        # pure data parallelism over the whole mesh: batch over every axis
        axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
        total = 1
        for a in axes:
            total *= mesh.shape[a]
        if B % total == 0:
            bspec = P(axes, *([None]))
    tok_shard = NamedSharding(mesh, bspec)
    step_shard = NamedSharding(mesh, P())

    abstract = [
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), params_abs, p_shard),
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), opt_abs, o_shard),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=step_shard),
        jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=tok_shard),
    ]
    in_shardings = [p_shard, o_shard, step_shard, tok_shard]
    if cfg.frontend:
        f_shard = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=2))
        abstract.append(
            jax.ShapeDtypeStruct((B, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16, sharding=f_shard)
        )
        in_shardings.append(f_shard)

    metric_shard = jax.tree.map(
        lambda _: step_shard, {"loss": 0, "ce": 0, "lb_loss": 0, "z_loss": 0, "grad_norm": 0}
    )
    out_shardings = (p_shard, o_shard, step_shard, metric_shard)

    return StepBundle(
        name="train_step",
        fn=train_step,
        in_shardings=tuple(in_shardings),
        out_shardings=out_shardings,
        abstract_inputs=tuple(abstract),
        donate_argnums=(0, 1),
        model=model,
    )


# ---------------------------------------------------------------------------
# prefill / serve
# ---------------------------------------------------------------------------


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, cell: ShapeCell) -> StepBundle:
    B, S = cell.global_batch, cell.seq_len
    model = build_model(cfg, mesh, B)

    def prefill_step(params, tokens, cache, frontend=None):
        return model.prefill(params, tokens, cache, frontend)

    params_abs = _abstract_params(cfg)
    p_shard = param_shardings(params_abs, mesh)
    cache_abs = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
    c_shard = cache_shardings(cache_abs, B, mesh)
    tok_shard = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=1))

    abstract = [
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), params_abs, p_shard),
        jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=tok_shard),
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), cache_abs, c_shard),
    ]
    in_shardings = [p_shard, tok_shard, c_shard]
    if cfg.frontend:
        f_shard = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=2))
        abstract.append(
            jax.ShapeDtypeStruct((B, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16, sharding=f_shard)
        )
        in_shardings.append(f_shard)

    logits_shard = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=1))
    out_shardings = (logits_shard, c_shard)

    return StepBundle(
        name="prefill_step",
        fn=prefill_step,
        in_shardings=tuple(in_shardings),
        out_shardings=out_shardings,
        abstract_inputs=tuple(abstract),
        donate_argnums=(2,),
        model=model,
    )


def build_serve_step(cfg: ModelConfig, mesh: Mesh, cell: ShapeCell) -> StepBundle:
    """One decode step: one new token per sequence against a seq_len cache.

    ``cfg.decode_plane`` selects the Agile decode plane (DecodePlan slots in
    the cache, capacity-sort-free MoE dispatch, valid-prefix attention).  It
    changes the cache pytree this bundle shards/donates (plan slots per MoE
    layer), so the prefill bundle that seeds the cache MUST be built from a
    config with the same ``decode_plane`` setting — set it on ``cfg`` before
    building either bundle (as launch/serve.py does), never on one side only.
    """
    B, S = cell.global_batch, cell.seq_len
    model = build_model(cfg, mesh, B)

    def serve_step(params, cache, tokens, cache_index):
        return model.decode_step(params, cache, tokens, cache_index)

    params_abs = _abstract_params(cfg)
    p_shard = param_shardings(params_abs, mesh)
    cache_abs = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
    c_shard = cache_shardings(cache_abs, B, mesh)
    tok_shard = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=0))
    scalar_shard = NamedSharding(mesh, P())

    abstract = (
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), params_abs, p_shard),
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), cache_abs, c_shard),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=tok_shard),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar_shard),
    )
    logits_shard = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=1))
    out_shardings = (logits_shard, c_shard)

    return StepBundle(
        name="serve_step",
        fn=serve_step,
        in_shardings=tuple(x for x in (p_shard, c_shard, tok_shard, scalar_shard)),
        out_shardings=out_shardings,
        abstract_inputs=abstract,
        donate_argnums=(1,),
        model=model,
    )


def build_spec_serve_step(
    cfg: ModelConfig, mesh: Mesh, cell: ShapeCell, *, telemetry: bool = False,
    tree: Optional[Any] = None,
) -> StepBundle:
    """One speculative/ragged serve launch: T = ``cfg.spec_tokens`` tokens per
    sequence against per-sequence cache lengths (continuous batching).

    The launch signature is ``(params, cache, tokens (B, T), lengths (B,),
    prev_accept (B,))`` -> ``(logits (B, T, V), cache[, metrics])`` —
    ``prev_accept`` selects each sequence's cache-carried plan row (the one
    computed from the route source of the position the previous launch's
    verification accepted).  As with ``build_serve_step``, the prefill bundle
    seeding the cache must be built from a config with identical
    ``decode_plane``/``spec_tokens`` settings (the plan-vector slots are part
    of the cache pytree).

    ``tree`` (a :class:`repro.core.plans.TreePlan` with ``num_nodes ==
    spec_tokens``) turns each launch into a draft-tree launch: the topology
    is compiled into the step closure (static under jit), the verifier walks
    it host-side, and ``prev_accept`` becomes the accepted node index.

    Under ``cfg.paged`` the launch takes one more control word: ``pages``,
    the replicated (B, max_pages) int32 block table.  A BRANCHY tree launch
    additionally takes the previous verify round's fused commit maps
    ``(dst, src)`` — the chain/no-tree step statically omits them, which is
    what keeps the paged chain path bitwise-identical to the contiguous one
    (no commit gather/scatter ever enters the compiled graph).
    """
    B, S = cell.global_batch, cell.seq_len
    Tn = max(cfg.spec_tokens, 1)
    if tree is not None and tree.num_nodes != Tn:
        raise ValueError(
            f"tree has {tree.num_nodes} nodes but cfg.spec_tokens is {Tn}"
        )
    model = build_model(cfg, mesh, B)
    branchy = tree is not None and not tree.is_chain()

    if cfg.paged and branchy:
        def spec_step(params, cache, tokens, lengths, prev_accept, pages, dst, src):
            return model.decode_tokens(
                params, cache, tokens, lengths, prev_accept, telemetry=telemetry,
                tree=tree, pages=pages, commit=(dst, src),
            )
    elif cfg.paged:
        def spec_step(params, cache, tokens, lengths, prev_accept, pages):
            return model.decode_tokens(
                params, cache, tokens, lengths, prev_accept, telemetry=telemetry,
                tree=tree, pages=pages,
            )
    else:
        def spec_step(params, cache, tokens, lengths, prev_accept):
            return model.decode_tokens(
                params, cache, tokens, lengths, prev_accept, telemetry=telemetry,
                tree=tree,
            )

    params_abs = _abstract_params(cfg)
    p_shard = param_shardings(params_abs, mesh)
    cache_abs = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
    c_shard = cache_shardings(cache_abs, B, mesh)
    tok_shard = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=1))
    vec_shard = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=0))
    rep_shard = NamedSharding(mesh, P())

    abstract = [
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), params_abs, p_shard),
        jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), cache_abs, c_shard),
        jax.ShapeDtypeStruct((B, Tn), jnp.int32, sharding=tok_shard),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=vec_shard),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=vec_shard),
    ]
    in_shardings = [p_shard, c_shard, tok_shard, vec_shard, vec_shard]
    if cfg.paged:
        # the block table is a control word: replicated, like the plan scalars
        mp = T.max_pages_for(cfg, S)
        abstract.append(jax.ShapeDtypeStruct((B, mp), jnp.int32, sharding=rep_shard))
        in_shardings.append(rep_shard)
        if branchy:
            for _ in ("dst", "src"):
                abstract.append(
                    jax.ShapeDtypeStruct((B, Tn), jnp.int32, sharding=tok_shard)
                )
                in_shardings.append(tok_shard)
    logits_shard = NamedSharding(mesh, batch_spec(B, mesh, extra_dims=2))
    out_shardings = (logits_shard, c_shard)
    if telemetry:
        out_shardings = out_shardings + ({"plan_agreement": NamedSharding(mesh, P())},)

    return StepBundle(
        name="spec_serve_step",
        fn=spec_step,
        in_shardings=tuple(in_shardings),
        out_shardings=out_shardings,
        abstract_inputs=tuple(abstract),
        donate_argnums=(1,),
        model=model,
    )


@dataclasses.dataclass
class AdmissionBundle:
    """The jitted B=1 admission path shared by every serve replica.

    Admission prefill runs at batch 1 (batch replicated; KV heads stay
    model-sharded) through a model whose collectives are built for batch=1 —
    the serve model's batch axes need not divide 1 — and ``admit`` writes the
    prefilled slot into the batch cache sharding-preservingly (donated
    ``dynamic_update_slice``, no host round trip).
    """

    prefill: Callable        # (params, tokens (1, L), one_cache[, frontend])
    one_cache_init: Callable  # () -> fresh B=1 cache allocated on the mesh
    admit: Callable          # (batch_cache, one_cache, slot) -> batch_cache
    model: Model             # the B=1 prefill model


def build_admission(
    cfg: ModelConfig, mesh: Mesh, serve_model: Model, max_len: int, cache_sharding: Any
) -> AdmissionBundle:
    """Under ``cfg.paged`` the B=1 prefill runs CONTIGUOUS (``paged=False``
    twin config — prefill writes stripes) and ``admit`` becomes the paged
    scatter :meth:`~repro.models.model.Model.write_cache_slot_paged`:
    ``admit(batch_cache, one_cache, slot, rows)`` with the host-computed
    physical-row vector — trie-shared pages send sentinel rows, so a
    trie-resident prompt admits with zero KV copies."""
    pf_cfg = dataclasses.replace(cfg, paged=False) if cfg.paged else cfg
    pf_model = build_model(pf_cfg, mesh, 1)
    c1_abs = jax.eval_shape(lambda: T.init_cache(pf_cfg, 1, max_len))
    c1_shard = cache_shardings(c1_abs, 1, mesh)
    lg1_shard = NamedSharding(mesh, batch_spec(1, mesh, extra_dims=1))
    prefill = jax.jit(pf_model.prefill, out_shardings=(lg1_shard, c1_shard))

    def one_cache_init():
        return T.init_cache(pf_cfg, 1, max_len)

    one_cache_init = jax.jit(one_cache_init, out_shardings=c1_shard)
    admit_fn = (
        serve_model.write_cache_slot_paged if cfg.paged else serve_model.write_cache_slot
    )
    admit = jax.jit(
        admit_fn, donate_argnums=(0,), out_shardings=cache_sharding
    )
    return AdmissionBundle(
        prefill=prefill, one_cache_init=one_cache_init, admit=admit, model=pf_model
    )


def build_step(cfg: ModelConfig, mesh: Mesh, cell: ShapeCell, *, strategy: str = "tp") -> StepBundle:
    if cell.step == "train":
        return build_train_step(cfg, mesh, cell, strategy=strategy)
    if cell.step == "prefill":
        return build_prefill_step(cfg, mesh, cell)
    if cell.step == "decode":
        return build_serve_step(cfg, mesh, cell)
    raise ValueError(cell.step)
