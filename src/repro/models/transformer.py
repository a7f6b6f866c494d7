"""Unified decoder stack for all assigned architectures.

Layer stacks are organised as *super-blocks* (one cycle of
``cfg.block_pattern``), scanned with ``jax.lax.scan`` so 94-layer models
compile one super-block regardless of depth; a remainder (pattern-incomplete
tail) is unrolled.

Control-flow plane integration: for MoE configs in ``lookahead`` mode the
scan carry is ``(x, route_src)`` — ``route_src`` is the previous layer's
residual stream, from which the *current* layer's dispatch plan is computed
at the top of the iteration, concurrently with the attention data plane
(Proactive PE Configuration).  ``moe_apply`` is injectable so the
distributed runtime can substitute the shard_map expert-parallel
implementation without touching stack logic.

Agile decode plane (``cfg.decode_plane``): decode steps leave the
prefill-shaped machinery entirely.  Each MoE layer's cache carries a
:class:`~repro.core.plans.DecodePlan` alongside its KV entries; the plan
consumed at step ``t`` was computed at step ``t-1`` (seeded by prefill for
``t=0``) from the same control-plane source stream — the router runs
temporally loosely-coupled, overlapping the previous step's FFN, and is a
pure cache read on the decode critical path.  The data plane is the
capacity-sort-free single-launch kernel (:mod:`repro.kernels.moe_decode`)
and attention reads only the valid cache prefix
(:mod:`repro.kernels.flash_attention.decode`).

Request-level control flow (:mod:`repro.core.programs`) rides the SAME host
control-word path as ``lengths``/``prev_accept`` and never enters this
stack: token-automaton state is derived per committed stream position, the
constraint mask is applied to the verify logits on the host, and rollback
under speculative rejection is exact because the length-clamp and commit
invariants already guarantee that only accepted rows are ever visible to
the next launch — a masked verified token occupies exactly the cache row an
unmasked one would, so fork/join and constrained decode need no kernel or
stack changes.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.control_plane import route_topk_decode, topk_agreement
from repro.core.plans import DecodePlan, TreePlan
from repro.core.quant import quantize_int8
from repro.kernels import use_kernels
from repro.models import layers as L
from repro.models import mamba2, moe, rglru

Params = Dict[str, Any]

# moe_apply(x_ffn, route_src, params) -> (y, aux_losses (2,))
MoeApply = Callable[[jnp.ndarray, Optional[jnp.ndarray], Params], Tuple[jnp.ndarray, jnp.ndarray]]

# decode_apply(x_ffn (B, S, d), plan, params) -> y (B, S, d): executes a
# cache-carried DecodePlan on the decode data plane.  Injectable so the
# distributed runtime can substitute the shard_map psum strategy
# (parallel.moe_parallel.make_sharded_decode_apply) — the single-host default
# is moe.moe_decode_ffn.  The router for the NEXT step stays in the layer
# (replicated f32 control math), only plan *execution* is distributed.
DecodeApply = Callable[[jnp.ndarray, DecodePlan, Params], jnp.ndarray]


@jax.custom_vjp
def _res(x: jnp.ndarray) -> jnp.ndarray:
    """Residual-stream barrier (perf iteration B-3, EXPERIMENTS.md §Perf).

    The next rms_norm upcasts the residual to f32; without a barrier XLA
    hoists that convert ABOVE the tensor-parallel all-reduce feeding the
    residual, doubling the wire bytes (f32 instead of bf16 collectives).
    optimization_barrier pins the convert below the all-reduce.

    custom_vjp because ``optimization_barrier`` has no differentiation rule
    on the oldest supported jax: semantically the barrier is the identity, so
    the fwd pass keeps the scheduling fence and the bwd pass passes
    cotangents straight through (no barrier on the gradient — the backward
    residual stream has its own collective schedule)."""
    return jax.lax.optimization_barrier(x)


def _res_fwd(x: jnp.ndarray):
    return _res(x), None


def _res_bwd(_, g):
    return (g,)


_res.defvjp(_res_fwd, _res_bwd)


def _default_moe_apply(cfg: ModelConfig) -> MoeApply:
    def apply(x_ffn, route_src, p):
        y, aux = moe.moe_layer(x_ffn, route_src, p, cfg)
        return y, jnp.stack([aux.load_balance_loss, aux.router_z_loss])

    return apply


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------


def init_layer(key, kind: str, cfg: ModelConfig, dtype) -> Params:
    d = cfg.d_model
    k1, k2, k3 = jax.random.split(key, 3)
    p: Params = {"ln1": L.init_rms_norm(d, jnp.float32), "ln2": L.init_rms_norm(d, jnp.float32)}
    if kind in ("attn", "local"):
        p["attn"] = L.init_attention(k1, cfg, dtype)
        p["ffn"] = L.init_swiglu(k2, d, cfg.d_ff, cfg.num_layers, dtype)
    elif kind == "moe":
        p["attn"] = L.init_attention(k1, cfg, dtype)
        p["moe"] = moe.init_moe(k2, cfg, dtype)
    elif kind == "rec":
        p["rec"] = rglru.init_rec_block(k1, cfg, dtype)
        p["ffn"] = L.init_swiglu(k2, d, cfg.d_ff, cfg.num_layers, dtype)
    elif kind == "ssm":
        p["ssm"] = mamba2.init_ssm_block(k1, cfg, dtype)
        del p["ln2"]  # mamba blocks have a single pre-norm
    else:
        raise ValueError(kind)
    return p


def init_params(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)
    kinds = cfg.layer_kinds
    pat = cfg.block_pattern
    n_sb, n_rest = divmod(cfg.num_layers, len(pat))
    keys = jax.random.split(key, cfg.num_layers + 3)

    def init_superblock(sb_key) -> Params:
        sub = jax.random.split(sb_key, len(pat))
        return {f"b{j}": init_layer(sub[j], pat[j], cfg, dtype) for j in range(len(pat))}

    sb_params = [init_superblock(keys[i]) for i in range(n_sb)]
    scan_params = jax.tree.map(lambda *xs: jnp.stack(xs), *sb_params) if n_sb > 1 else (
        jax.tree.map(lambda x: x[None], sb_params[0]) if n_sb == 1 else {}
    )
    rest_params = [init_layer(keys[n_sb + j], kinds[n_sb * len(pat) + j], cfg, dtype) for j in range(n_rest)]

    params: Params = {
        "embed": L.init_embedding(keys[-1], cfg.vocab_size, cfg.d_model, dtype),
        "blocks": {"scan": scan_params, "rest": rest_params},
        "final_norm": L.init_rms_norm(cfg.d_model, jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.init_embedding(keys[-2], cfg.vocab_size, cfg.d_model, dtype)
    if cfg.frontend:
        params["frontend"] = {
            "proj": L.dense_init(keys[-3], cfg.frontend_dim, cfg.d_model, dtype=dtype)
        }
    return params


# ---------------------------------------------------------------------------
# cache / state init
# ---------------------------------------------------------------------------


def max_pages_for(cfg: ModelConfig, max_len: int) -> int:
    """Block-table width: logical pages covering one slot's max_len."""
    return -(-int(max_len) // int(cfg.page_size))


def num_pages(cfg: ModelConfig, batch: int, max_len: int) -> int:
    """Default physical pool size: the contiguous capacity, in pages."""
    return batch * max_pages_for(cfg, max_len)


def identity_page_table(cfg: ModelConfig, batch: int, max_len: int) -> jnp.ndarray:
    """The (B, max_pages) block table reproducing the contiguous layout:
    slot b's logical page i is physical page ``b * max_pages + i``.  Used by
    benchmarks/tests without the serve allocator — with it the paged chain
    path is bitwise-identical to the contiguous path."""
    mp = max_pages_for(cfg, max_len)
    return (
        jnp.arange(batch, dtype=jnp.int32)[:, None] * mp
        + jnp.arange(mp, dtype=jnp.int32)[None, :]
    )


def init_layer_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int, dtype) -> Params:
    if kind in ("attn", "local", "moe"):
        window = cfg.local_window if (kind == "local" or cfg.attention_kind == "local") else 0
        # Rolling buffers need spec_tokens - 1 slack slots: a speculative
        # launch writes all T draft tokens before attending, and with exactly
        # ``window`` slots the later drafts would evict positions still
        # inside the earlier drafts' windows (sequential decode sees them).
        # Rounded to 8 so the window kernel keeps a block-aligned buffer.
        spec_slack = -(-(max(int(cfg.spec_tokens), 1) - 1) // 8) * 8
        S = min(max_len, window + spec_slack) if window else max_len
        hd = cfg.resolved_head_dim
        # Quantized bandwidth plane: int8 KV rows with per-token f32 scale
        # leaves ("ks"/"vs": (B, S); paged "pks"/"pvs": (R,)) — the scale
        # rows follow their KV tile into the kernel, and per-TOKEN
        # granularity is what keeps speculative rollback / draft overwrite /
        # paged CoW token-identical to sequential decode (a per-block scale
        # would couple rows that move independently).
        quant = cfg.kv_dtype == "int8"
        kv_dt = jnp.int8 if quant else dtype
        if cfg.paged and not window:
            # Paged KV plane: full-attention KV lives in a flat physical page
            # pool (NO batch axis) addressed through the per-slot block table
            # that rides the launch as a control word.  The default pool
            # matches the contiguous capacity (batch * ceil(max_len/ps)
            # pages); the serve allocator shares/evicts pages within it.
            # Rolling caches stay modulo-addressed — their byte bound is the
            # window, and paging a W-sized buffer would buy nothing.
            pages = num_pages(cfg, batch, max_len)
            c = {
                "pk": jnp.zeros((pages * cfg.page_size, cfg.num_kv_heads, hd), kv_dt),
                "pv": jnp.zeros((pages * cfg.page_size, cfg.num_kv_heads, hd), kv_dt),
            }
            if quant:
                c["pks"] = jnp.ones((pages * cfg.page_size,), jnp.float32)
                c["pvs"] = jnp.ones((pages * cfg.page_size,), jnp.float32)
        else:
            c = {
                "k": jnp.zeros((batch, S, cfg.num_kv_heads, hd), kv_dt),
                "v": jnp.zeros((batch, S, cfg.num_kv_heads, hd), kv_dt),
            }
            if quant:
                c["ks"] = jnp.ones((batch, S), jnp.float32)
                c["vs"] = jnp.ones((batch, S), jnp.float32)
        if kind == "moe" and cfg.decode_plane:
            # Agile decode plane: the layer's next-step DecodePlan lives in
            # the cache alongside the KV entries (uniform placeholder until
            # prefill seeds it from the prompt's last control-plane source).
            # With spec_tokens > 1 the cache carries one plan row per draft
            # position, so the next launch can consume the row matching the
            # verified/accepted prefix (rollback-exact plan selection).
            Tp = max(int(cfg.spec_tokens), 1)
            shape = (batch, Tp, cfg.top_k) if Tp > 1 else (batch, cfg.top_k)
            c["plan_e"] = jnp.zeros(shape, jnp.int32)
            c["plan_w"] = jnp.full(shape, 1.0 / cfg.top_k, jnp.float32)
        return c
    if kind == "rec":
        return rglru.init_rec_state(batch, cfg, dtype)
    if kind == "ssm":
        return mamba2.init_ssm_state(batch, cfg, dtype)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Decode cache pytree mirroring the params blocks structure.

    For ``local`` attention the cache is a rolling window buffer of size
    ``local_window`` (sub-quadratic memory: this is what makes long_500k
    feasible for hybrid archs).
    """
    dtype = jnp.dtype(cfg.dtype)
    pat = cfg.block_pattern
    n_sb, n_rest = divmod(cfg.num_layers, len(pat))
    kinds = cfg.layer_kinds

    def one_sb():
        return {f"b{j}": init_layer_cache(pat[j], cfg, batch, max_len, dtype) for j in range(len(pat))}

    scan_cache = (
        jax.tree.map(lambda *xs: jnp.stack(xs), *[one_sb() for _ in range(n_sb)])
        if n_sb > 1
        else (jax.tree.map(lambda x: x[None], one_sb()) if n_sb == 1 else {})
    )
    rest_cache = [
        init_layer_cache(kinds[n_sb * len(pat) + j], cfg, batch, max_len, dtype) for j in range(n_rest)
    ]
    return {"scan": scan_cache, "rest": rest_cache}


# ---------------------------------------------------------------------------
# block application
# ---------------------------------------------------------------------------


def apply_layer_train(
    x: jnp.ndarray,
    route_src: Optional[jnp.ndarray],
    p: Params,
    kind: str,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    moe_apply: MoeApply,
) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], jnp.ndarray]:
    """One layer, train/prefill-style full-sequence pass (no cache)."""
    aux = jnp.zeros((2,), jnp.float32)
    if kind in ("attn", "local", "moe"):
        window = cfg.local_window if (kind == "local" or cfg.attention_kind == "local") else 0
        a, _ = L.attention_block(
            L.rms_norm(x, p["ln1"]), p["attn"], cfg, positions=positions, local_window=window
        )
        h = _res(x + a)
        ffn_in = L.rms_norm(h, p["ln2"])
        if kind == "moe":
            y, aux = moe_apply(ffn_in, route_src, p["moe"])
            route_src = h  # next layer's control-plane source
        else:
            y = L.swiglu(ffn_in, p["ffn"])
        x = _res(h + y)
    elif kind == "rec":
        h = _res(x + rglru.rec_block(L.rms_norm(x, p["ln1"]), p["rec"], cfg))
        x = _res(h + L.swiglu(L.rms_norm(h, p["ln2"]), p["ffn"]))
    elif kind == "ssm":
        x = _res(x + mamba2.ssm_block(L.rms_norm(x, p["ln1"]), p["ssm"], cfg))
    else:
        raise ValueError(kind)
    return x, route_src, aux


def apply_layer_prefill(
    x: jnp.ndarray,
    route_src: Optional[jnp.ndarray],
    p: Params,
    cache: Params,
    kind: str,
    cfg: ModelConfig,
    positions: jnp.ndarray,
    moe_apply: MoeApply,
):
    """Like train, but fills the decode cache and returns it."""
    aux = jnp.zeros((2,), jnp.float32)
    if kind in ("attn", "local", "moe"):
        window = cfg.local_window if (kind == "local" or cfg.attention_kind == "local") else 0
        if "pk" in cache:
            raise ValueError(
                "prefill writes contiguous stripes; paged caches are seeded "
                "through the admission path (B=1 contiguous prefill + page "
                "scatter) — build the prefill model with paged=False"
            )
        xn = L.rms_norm(x, p["ln1"])
        q, k, v = L._qkv(xn, p["attn"], cfg, positions)
        S = x.shape[1]
        W = cache["k"].shape[1]
        # write the last min(W, S) positions at rolling slots (pos % W), so
        # decode's rolling-window addressing continues seamlessly
        take = min(W, S)
        slots = jnp.arange(S - take, S, dtype=jnp.int32) % W
        kw, vw = k[:, -take:], v[:, -take:]
        new_cache = {}
        if "ks" in cache:
            # quantize at admission: the attention math above stays full
            # precision (prefill logits are exact); only the CACHE rows are
            # int8 + per-token scale control words, so every decode step —
            # speculative or sequential — reads the same quantized prefix
            kw, vw, ksr, vsr = _quant_kv_rows(kw, vw)
            new_cache["ks"] = cache["ks"].at[:, slots].set(ksr)
            new_cache["vs"] = cache["vs"].at[:, slots].set(vsr)
        ck = cache["k"].at[:, slots].set(kw.astype(cache["k"].dtype))
        cv = cache["v"].at[:, slots].set(vw.astype(cache["v"].dtype))
        new_cache["k"], new_cache["v"] = ck, cv
        if use_kernels(cfg):
            from repro.kernels.flash_attention import flash_attention

            out = flash_attention(q, k, v, causal=True, window=window)
        else:
            out = L.blockwise_attention(
                q, k, v, causal=True, local_window=window, unroll=cfg.analysis_unroll
            )
        h = _res(x + jnp.einsum("bsnh,nhd->bsd", out, p["attn"]["wo"].astype(out.dtype)))
        ffn_in = L.rms_norm(h, p["ln2"])
        if kind == "moe":
            if cfg.decode_plane:
                # seed the first decode step's plan from the prompt's last
                # control-plane source (the same route_src stream decode
                # consumes one step later) — plan rides the cache from here on
                src = (route_src if route_src is not None else h)[:, -1, :]
                seed = route_topk_decode(src, p["moe"]["router"], cfg.top_k)
                if cfg.spec_tokens > 1:
                    # plan-vector carry: every draft position of the first
                    # launch starts from the same prefill-seeded plan
                    B_, Tp, k_ = x.shape[0], cfg.spec_tokens, cfg.top_k
                    new_cache["plan_e"] = jnp.broadcast_to(
                        seed.expert_ids[:, None], (B_, Tp, k_)
                    ).astype(jnp.int32)
                    new_cache["plan_w"] = jnp.broadcast_to(
                        seed.weights[:, None], (B_, Tp, k_)
                    ).astype(jnp.float32)
                else:
                    new_cache["plan_e"] = seed.expert_ids
                    new_cache["plan_w"] = seed.weights
            y, aux = moe_apply(ffn_in, route_src, p["moe"])
            route_src = h
        else:
            y = L.swiglu(ffn_in, p["ffn"])
        x = _res(h + y)
    elif kind == "rec":
        r, new_cache = rglru.rec_block_prefill(L.rms_norm(x, p["ln1"]), p["rec"], cfg)
        h = _res(x + r)
        x = _res(h + L.swiglu(L.rms_norm(h, p["ln2"]), p["ffn"]))
    elif kind == "ssm":
        s, new_cache = mamba2.ssm_block(L.rms_norm(x, p["ln1"]), p["ssm"], cfg, return_state=True)
        x = _res(x + s)
    else:
        raise ValueError(kind)
    return x, route_src, new_cache, aux


def apply_layer_decode(
    x: jnp.ndarray,  # (B, 1, d)
    route_src: Optional[jnp.ndarray],
    p: Params,
    cache: Params,
    kind: str,
    cfg: ModelConfig,
    cache_index: jnp.ndarray,  # scalar int32
    moe_apply: MoeApply,
    decode_apply: Optional[DecodeApply] = None,
):
    aux = jnp.zeros((2,), jnp.float32)
    if kind in ("attn", "local", "moe"):
        window = cfg.local_window if (kind == "local" or cfg.attention_kind == "local") else 0
        if "pk" in cache:
            raise ValueError(
                "paged caches decode through Model.decode_tokens (the block "
                "table is a launch argument); decode_step has no page-table "
                "plumbing — use spec width 1 through decode_tokens instead"
            )
        xn = L.rms_norm(x, p["ln1"])
        if cfg.decode_plane and not window:
            # Agile decode plane: full-attention caches are prefix-valid, so
            # the length-steered kernel/jnp path reads only [0, cache_index]
            a, new_cache = _decode_attn_prefix(xn, p["attn"], cfg, cache, cache_index)
        else:
            a, new_cache = _decode_attn_rolling(xn, p["attn"], cfg, cache, cache_index, window)
        h = _res(x + a)
        ffn_in = L.rms_norm(h, p["ln2"])
        if kind == "moe":
            if cfg.decode_plane:
                # consume the cache-carried plan (computed during the
                # previous step — control is off this step's critical path),
                # then run the router for the NEXT step from this step's
                # control-plane source, overlapping this layer's FFN
                plan = DecodePlan(cache["plan_e"], cache["plan_w"])
                y = (decode_apply or moe.moe_decode_ffn)(ffn_in, plan, p["moe"])
                src = (route_src if route_src is not None else h)[:, -1, :]
                nxt = route_topk_decode(src, p["moe"]["router"], cfg.top_k)
                new_cache["plan_e"] = nxt.expert_ids
                new_cache["plan_w"] = nxt.weights
            else:
                y, aux = moe_apply(ffn_in, route_src, p["moe"])
            route_src = h
        else:
            y = L.swiglu(ffn_in, p["ffn"])
        x = _res(h + y)
    elif kind == "rec":
        r, new_cache = rglru.rec_block_decode(L.rms_norm(x, p["ln1"]), p["rec"], cfg, cache)
        h = _res(x + r)
        x = _res(h + L.swiglu(L.rms_norm(h, p["ln2"]), p["ffn"]))
    elif kind == "ssm":
        s, new_cache = mamba2.ssm_block_decode(L.rms_norm(x, p["ln1"]), p["ssm"], cfg, cache)
        x = _res(x + s)
    else:
        raise ValueError(kind)
    return x, route_src, new_cache, aux


def apply_layer_decode_spec(
    x: jnp.ndarray,  # (B, T, d) — T draft tokens per sequence, one launch
    route_src: Optional[jnp.ndarray],
    p: Params,
    cache: Params,
    kind: str,
    cfg: ModelConfig,
    lengths: jnp.ndarray,  # (B,) int32 per-sequence cache length (ragged batch)
    prev_accept: jnp.ndarray,  # (B,) int32 accepted-row index into the plan vector
    moe_apply: MoeApply,
    *,
    decode_apply: Optional[DecodeApply] = None,
    telemetry: bool = False,
    tree: Optional[TreePlan] = None,
    pages: Optional[jnp.ndarray] = None,  # (B, max_pages) int32 block table
    commit: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # (dst, src) (B, Tc)
):
    """Multi-token (speculative) ragged decode for one layer.

    Token (b, t) sits at absolute position ``lengths[b] + t``.  The
    per-token position vector is the layer's control word: attention clamps
    each token's KV walk against it (vector-steered flash-decode), and the
    MoE plan vector is indexed by it.  Plan semantics reproduce T sequential
    single-token steps exactly:

    * token 0 consumes the cache-carried plan row selected by
      ``prev_accept`` (the row computed, last launch, from the route source
      of the position that verification actually accepted — rollback-exact);
    * token t >= 1 consumes the plan routed from this launch's route source
      at position t-1 (the same source a sequential step t-1 would have
      written to the cache);
    * all T routed plans are written back as the next launch's plan vector.

    With ``tree`` (a :class:`~repro.core.plans.TreePlan`) the T tokens form
    a draft *tree* instead of a chain: node t occupies cache row
    ``lengths[b] + t`` but rotary position ``lengths[b] + depth(t)``,
    attention masks draft rows by the tree's ancestor table (the committed
    prefix stays shared), and the plan consumed by node t >= 1 is the one
    routed from its PARENT's route source — each root-to-node path
    reproduces the sequential trace for that token sequence exactly.  The
    degenerate chain tree takes this same code path and is bitwise-equal to
    ``tree=None``.

    Paged caches (``"pk"``/``"pv"`` pool leaves) additionally take ``pages``
    — the per-slot block table, a launch-argument control word — steering
    writes and reads through logical→physical row translation, and
    ``commit`` — the previous verify round's accepted-path row moves
    ``(dst, src)`` in LOGICAL positions (-1 = no-op), fused into this
    launch ahead of any new writes so tree commit never needs its own
    launch.  Under ``cfg.paged`` branchy trees are also served on
    rolling-window layers (the fused commit maps compose with modulo
    addressing); the legacy non-paged path keeps the chain-only
    restriction.

    Returns ``(x, route_src, new_cache, plan_agreement)`` where
    ``plan_agreement`` is the stale-vs-fresh top-k overlap (0 when not a MoE
    layer or telemetry is off).
    """
    agree = jnp.float32(0.0)
    B, T, d = x.shape
    if kind in ("attn", "local", "moe"):
        window = cfg.local_window if (kind == "local" or cfg.attention_kind == "local") else 0
        paged = "pk" in cache
        if paged and pages is None:
            raise ValueError(
                "paged cache without a block table: pass pages=(B, max_pages) "
                "int32 (see models.transformer.identity_page_table)"
            )
        if commit is not None:
            # fused tree commit: apply the previous verify round's accepted
            # row moves before this launch writes new draft rows — the dst
            # rows [L_old, L_new) are disjoint from this launch's writes
            # [L_new, L_new + T), and gather-before-scatter makes overlapping
            # (dst, src) windows safe
            cache = _apply_commit(cache, commit, pages, cfg)
        if tree is not None and window and tree.is_chain():
            tree = None  # chains serve through the linear rolling path
        if tree is not None and window and not cfg.paged:
            raise NotImplementedError(
                "branchy draft trees on rolling-window layers need the paged "
                "KV plane's fused commit maps (cfg.paged=True); the legacy "
                "contiguous path serves local-attention archs with chain "
                "drafts only"
            )
        xn = L.rms_norm(x, p["ln1"])
        if tree is not None and window:
            a, new_cache = _decode_attn_rolling_tree(
                xn, p["attn"], cfg, cache, lengths, window, tree
            )
        elif tree is not None and paged:
            a, new_cache = _decode_attn_paged_tree(
                xn, p["attn"], cfg, cache, lengths, tree, pages
            )
        elif tree is not None:
            a, new_cache = _decode_attn_prefix_tree(xn, p["attn"], cfg, cache, lengths, tree)
        elif window:
            a, new_cache = _decode_attn_rolling_spec(xn, p["attn"], cfg, cache, lengths, window)
        elif paged:
            a, new_cache = _decode_attn_paged_spec(xn, p["attn"], cfg, cache, lengths, pages)
        else:
            a, new_cache = _decode_attn_prefix_spec(xn, p["attn"], cfg, cache, lengths)
        h = _res(x + a)
        ffn_in = L.rms_norm(h, p["ln2"])
        if kind == "moe":
            if cfg.decode_plane:
                src_seq = route_src if route_src is not None else h  # (B, T, d)
                k_ = cfg.top_k
                # one router launch covers draft routing AND next-launch plans
                nxt = route_topk_decode(
                    src_seq.reshape(B * T, d), p["moe"]["router"], k_
                )
                all_e = nxt.expert_ids.reshape(B, T, k_)
                all_w = nxt.weights.reshape(B, T, k_)
                cached_e, cached_w = cache["plan_e"], cache["plan_w"]
                if cached_e.ndim == 3:
                    sel = prev_accept[:, None, None]
                    first_e = jnp.take_along_axis(cached_e, sel, axis=1)[:, 0]
                    first_w = jnp.take_along_axis(cached_w, sel, axis=1)[:, 0]
                else:  # spec_tokens == 1 cache: single temporal plan row
                    first_e, first_w = cached_e, cached_w
                if tree is not None:
                    # plan-row selection follows the accepted ancestor chain:
                    # node t consumes the plan routed from its parent's route
                    # source (the sequential predecessor on its root path),
                    # not row t-1 (a chain tree gathers rows 0..T-2: bitwise
                    # the linear concatenate-shift)
                    par = jnp.asarray(
                        [max(pp, 0) for pp in tree.parents], jnp.int32
                    )
                    sel_p = jnp.broadcast_to(par[None, :, None], (B, T, k_))
                    prev_e = jnp.take_along_axis(all_e, sel_p, axis=1)
                    prev_w = jnp.take_along_axis(all_w, sel_p, axis=1)
                    cons_e = jnp.concatenate([first_e[:, None], prev_e[:, 1:]], axis=1)
                    cons_w = jnp.concatenate([first_w[:, None], prev_w[:, 1:]], axis=1)
                else:
                    cons_e = jnp.concatenate([first_e[:, None], all_e[:, : T - 1]], axis=1)
                    cons_w = jnp.concatenate([first_w[:, None], all_w[:, : T - 1]], axis=1)
                plan = DecodePlan(cons_e, cons_w)  # (B, T, k): one row per draft
                y = (decode_apply or moe.moe_decode_ffn)(ffn_in, plan, p["moe"])
                if cached_e.ndim == 3:
                    new_cache["plan_e"] = all_e
                    new_cache["plan_w"] = all_w
                else:
                    new_cache["plan_e"] = all_e[:, -1]
                    new_cache["plan_w"] = all_w[:, -1]
                if telemetry:
                    # stale (consumed, position t-1 source) vs fresh (same
                    # position source) — the decode-plane lookahead bet
                    agree = topk_agreement(
                        cons_e.reshape(B * T, k_), all_e.reshape(B * T, k_)
                    )
            else:
                y, _ = moe_apply(ffn_in, route_src, p["moe"])
            route_src = h
        else:
            y = L.swiglu(ffn_in, p["ffn"])
        x = _res(h + y)
    elif kind in ("rec", "ssm"):
        # stateful recurrences advance one token per launch: supported at
        # spec width 1 (ragged continuous batching without drafts)
        if T != 1:
            raise NotImplementedError(
                f"multi-token decode for {kind!r} layers needs a T-step state "
                "recurrence; serve rec/ssm archs with spec_tokens=1"
            )
        if kind == "rec":
            r, new_cache = rglru.rec_block_decode(L.rms_norm(x, p["ln1"]), p["rec"], cfg, cache)
            h = _res(x + r)
            x = _res(h + L.swiglu(L.rms_norm(h, p["ln2"]), p["ffn"]))
        else:
            s, new_cache = mamba2.ssm_block_decode(L.rms_norm(x, p["ln1"]), p["ssm"], cfg, cache)
            x = _res(x + s)
    else:
        raise ValueError(kind)
    return x, route_src, new_cache, agree


def _spec_positions(lengths: jnp.ndarray, T: int) -> jnp.ndarray:
    """(B,) per-sequence lengths -> (B, T) absolute position per draft token."""
    return lengths[:, None].astype(jnp.int32) + jnp.arange(T, dtype=jnp.int32)[None, :]


# ---------------------------------------------------------------------------
# quantized bandwidth plane: per-token int8 KV rows + scale control words
# ---------------------------------------------------------------------------


def _quant_kv_rows(k: jnp.ndarray, v: jnp.ndarray):
    """Quantize new KV rows per TOKEN: (..., nkv, hd) -> int8 rows + one f32
    scale per row.  The row is the unit speculative rollback, tree commit,
    and paged CoW move, so quantizing at row granularity keeps every cache
    mutation a plain (int8-row, scale) pair move — bit-identical under any
    reordering the serve plane performs."""
    kq, ks_ = quantize_int8(k.astype(jnp.float32), axis=(-2, -1))
    vq, vs_ = quantize_int8(v.astype(jnp.float32), axis=(-2, -1))
    return kq, vq, ks_[..., 0, 0].astype(jnp.float32), vs_[..., 0, 0].astype(jnp.float32)


def _deq(c: jnp.ndarray, s: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Dequantized f32 view of a (..., nkv, hd) cache buffer for the
    masked-jnp paths: the jnp twins dequantize the buffer then run the
    existing full-precision math — the kernel path's dequant-after-tile-load
    is bitwise-equal to exactly this."""
    if s is None:
        return c
    return c.astype(jnp.float32) * s[..., None, None].astype(jnp.float32)


def _decode_attn_prefix_spec(
    xn: jnp.ndarray,  # (B, T, d)
    p: Params,
    cfg: ModelConfig,
    cache: Params,
    lengths: jnp.ndarray,  # (B,)
) -> Tuple[jnp.ndarray, Params]:
    """T-token attention over per-token valid prefixes [0, lengths[b] + t].

    The per-token clamp doubles as the intra-draft causal mask: draft token t
    sees draft tokens < t (already written to the cache) and nothing after.
    """
    B, T, _ = xn.shape
    pos = _spec_positions(lengths, T)
    q, k, v = L._qkv(xn, p, cfg, pos)
    bidx = jnp.arange(B)[:, None]
    cks = cvs = None
    if "ks" in cache:
        k, v, ksr, vsr = _quant_kv_rows(k, v)
        cks = cache["ks"].at[bidx, pos].set(ksr)
        cvs = cache["vs"].at[bidx, pos].set(vsr)
    ck = cache["k"].at[bidx, pos].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, pos].set(v.astype(cache["v"].dtype))
    if use_kernels(cfg):
        from repro.kernels.flash_attention import flash_decode

        scl = None if cks is None else jnp.stack([cks, cvs])
        out = flash_decode(q, ck, cv, pos, scales=scl)  # (B, T, nq, hd)
    else:
        S = ck.shape[1]
        hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
        groups = cfg.num_heads // nkv
        ckf, cvf = _deq(ck, cks), _deq(cv, cvs)
        valid = jnp.arange(S)[None, None, :] <= pos[:, :, None]  # (B, T, S)
        scale = 1.0 / math.sqrt(hd)
        qg = q.reshape(B, T, nkv, groups, hd)
        s = jnp.einsum("btngh,bsnh->bngts", qg.astype(jnp.float32), ckf.astype(jnp.float32)) * scale
        s = jnp.where(valid[:, None, None, :, :], s, L.NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bngts,bsnh->btngh", w, cvf.astype(jnp.float32))
        out = out.reshape(B, T, cfg.num_heads, hd).astype(xn.dtype)
    y = jnp.einsum("btnh,nhd->btd", out, p["wo"].astype(out.dtype))
    nc = {"k": ck, "v": cv}
    if cks is not None:
        nc["ks"], nc["vs"] = cks, cvs
    return y, nc


def _decode_attn_prefix_tree(
    xn: jnp.ndarray,  # (B, T, d) — T draft-tree nodes per sequence
    p: Params,
    cfg: ModelConfig,
    cache: Params,
    lengths: jnp.ndarray,  # (B,) committed-prefix length per sequence
    tree: "TreePlan",
) -> Tuple[jnp.ndarray, Params]:
    """Ancestor-masked T-node attention: the tree generalization of
    :func:`_decode_attn_prefix_spec`.

    Node t occupies cache ROW ``lengths[b] + t`` (each node needs its own KV
    slot — siblings share a depth) but rotary POSITION
    ``lengths[b] + depth(t)`` (its sequential position if accepted).  A row
    is visible to node t iff it is below the committed prefix or on t's root
    path — exactly the keys a sequential decode of that path would see, so
    each root-to-node chain scores identically to sequential decode.
    """
    B, T, _ = xn.shape
    depths = jnp.asarray(tree.depths(), jnp.int32)
    rows = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B, T)
    pos = lengths[:, None] + depths[None, :]  # rotary positions
    q, k, v = L._qkv(xn, p, cfg, pos)
    bidx = jnp.arange(B)[:, None]
    cks = cvs = None
    if "ks" in cache:
        k, v, ksr, vsr = _quant_kv_rows(k, v)
        cks = cache["ks"].at[bidx, rows].set(ksr)
        cvs = cache["vs"].at[bidx, rows].set(vsr)
    ck = cache["k"].at[bidx, rows].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, rows].set(v.astype(cache["v"].dtype))
    if use_kernels(cfg):
        from repro.kernels.flash_attention import flash_decode

        scl = None if cks is None else jnp.stack([cks, cvs])
        out = flash_decode(
            q, ck, cv, lengths,
            ancestors=jnp.asarray(tree.ancestor_words(), jnp.int32),
            base=lengths, scales=scl,
        )  # (B, T, nq, hd)
    else:
        S = ck.shape[1]
        hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
        groups = cfg.num_heads // nkv
        ckf, cvf = _deq(ck, cks), _deq(cv, cvs)
        table = jnp.asarray(tree.ancestor_table(), bool)  # (T, T)
        u = jnp.arange(S)[None, :] - lengths[:, None]  # (B, S) draft-row index
        in_draft = (u >= 0) & (u < T)
        anc_ok = table[:, jnp.clip(u, 0, T - 1)]  # (T, B, S)
        valid = (u < 0)[:, None, :] | (
            in_draft[:, None, :] & jnp.transpose(anc_ok, (1, 0, 2))
        )  # (B, T, S)
        scale = 1.0 / math.sqrt(hd)
        qg = q.reshape(B, T, nkv, groups, hd)
        s = jnp.einsum("btngh,bsnh->bngts", qg.astype(jnp.float32), ckf.astype(jnp.float32)) * scale
        s = jnp.where(valid[:, None, None, :, :], s, L.NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bngts,bsnh->btngh", w, cvf.astype(jnp.float32))
        out = out.reshape(B, T, cfg.num_heads, hd).astype(xn.dtype)
    y = jnp.einsum("btnh,nhd->btd", out, p["wo"].astype(out.dtype))
    nc = {"k": ck, "v": cv}
    if cks is not None:
        nc["ks"], nc["vs"] = cks, cvs
    return y, nc


def _decode_attn_rolling_spec(
    xn: jnp.ndarray,  # (B, T, d)
    p: Params,
    cfg: ModelConfig,
    cache: Params,
    lengths: jnp.ndarray,  # (B,)
    window: int,
) -> Tuple[jnp.ndarray, Params]:
    """T-token attention against a rolling (modulo-addressed) KV cache.

    All T tokens are written at slots ``pos % W`` first; each query then
    masks by absolute position reconstructed from the final write head, so
    draft token t never sees draft tokens written after it.  Requires
    T <= W (a draft longer than the window would overwrite its own slots).
    """
    B, T, _ = xn.shape
    W = cache["k"].shape[1]
    assert T <= W, "draft length must not exceed the rolling window"
    pos = _spec_positions(lengths, T)
    q, k, v = L._qkv(xn, p, cfg, pos)
    bidx = jnp.arange(B)[:, None]
    slots = jnp.remainder(pos, W)
    cks = cvs = None
    if "ks" in cache:
        k, v, ksr, vsr = _quant_kv_rows(k, v)
        cks = cache["ks"].at[bidx, slots].set(ksr)
        cvs = cache["vs"].at[bidx, slots].set(vsr)
    ck = cache["k"].at[bidx, slots].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slots].set(v.astype(cache["v"].dtype))
    limit = min(window, W) if window else W
    if cfg.decode_plane and use_kernels(cfg):
        from repro.kernels.flash_attention import flash_decode_window

        scl = None if cks is None else jnp.stack([cks, cvs])
        out = flash_decode_window(q, ck, cv, lengths, window=limit, scales=scl)
    else:
        hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
        groups = cfg.num_heads // nkv
        ckf, cvf = _deq(ck, cks), _deq(cv, cvs)
        head = pos[:, -1]  # (B,) last written absolute position
        slot = jnp.arange(W)
        write = jnp.remainder(head, W)
        # absolute position stored in slot s: largest p <= head with p % W == s
        abs_pos = head[:, None] - jnp.remainder(write[:, None] - slot[None, :], W)  # (B, W)
        valid = (
            (abs_pos[:, None, :] >= 0)
            & (abs_pos[:, None, :] <= pos[:, :, None])
            & (abs_pos[:, None, :] > pos[:, :, None] - limit)
        )  # (B, T, W)
        scale = 1.0 / math.sqrt(hd)
        qg = q.reshape(B, T, nkv, groups, hd)
        s = jnp.einsum("btngh,bsnh->bngts", qg.astype(jnp.float32), ckf.astype(jnp.float32)) * scale
        s = jnp.where(valid[:, None, None, :, :], s, L.NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bngts,bsnh->btngh", w, cvf.astype(jnp.float32))
        out = out.reshape(B, T, cfg.num_heads, hd).astype(xn.dtype)
    y = jnp.einsum("btnh,nhd->btd", out, p["wo"].astype(out.dtype))
    nc = {"k": ck, "v": cv}
    if cks is not None:
        nc["ks"], nc["vs"] = cks, cvs
    return y, nc


def _paged_rows(pages: jnp.ndarray, pos: jnp.ndarray, ps: int, R: int) -> jnp.ndarray:
    """Translate logical positions to physical pool rows via the block table.

    ``pages`` is (B, max_pages) int32 (-1 = unallocated); ``pos`` is (B, T)
    logical absolute positions.  Unmapped positions resolve to row ``R`` —
    one past the pool — so callers can scatter with ``mode="drop"`` (JAX
    would WRAP a negative row back into the pool; the sentinel must be
    out-of-bounds POSITIVE).
    """
    idx = jnp.minimum(pos // ps, pages.shape[1] - 1)
    phys = jnp.take_along_axis(pages, idx, axis=1)  # (B, T)
    return jnp.where(
        phys >= 0, phys * ps + jnp.remainder(pos, ps), R
    ).astype(jnp.int32)


def _paged_view(pool: jnp.ndarray, pages: jnp.ndarray, ps: int) -> jnp.ndarray:
    """Gather the flat pool into the per-slot contiguous layout
    (B, max_pages * ps, ...) the masked-jnp attention paths expect.  Unmapped
    pages gather page 0 — callers mask those columns out."""
    B, mp = pages.shape
    safe = jnp.where(pages >= 0, pages, 0)
    rows = (safe * ps)[:, :, None] + jnp.arange(ps, dtype=jnp.int32)[None, None, :]
    return pool[rows.reshape(B, mp * ps)]


def _apply_commit(
    cache: Params,
    commit: Tuple[jnp.ndarray, jnp.ndarray],
    pages: Optional[jnp.ndarray],
    cfg: ModelConfig,
) -> Params:
    """Fused tree commit: move the accepted path's KV rows, one gather and
    one scatter, at the top of the decode launch (before any new writes).

    ``commit = (dst, src)`` are (B, Tc) LOGICAL absolute positions with -1 as
    the no-op sentinel (see :func:`repro.core.pages.commit_maps`).  Pool
    caches translate through the block table — only rows inside the boundary
    page ever move, full pages were rewired on the host for free; rolling
    caches move rows modulo W.  Gather-before-scatter makes overlapping
    (dst, src) windows safe; sentinels become positive out-of-bounds rows so
    ``mode="drop"`` discards them (negative indices would wrap).
    """
    dst, src = commit
    new_cache = dict(cache)
    if "pk" in cache:
        ck, cv = cache["pk"], cache["pv"]
        R = ck.shape[0]
        ps = cfg.page_size
        src_rows = jnp.minimum(_paged_rows(pages, jnp.maximum(src, 0), ps, R), R - 1)
        dst_rows = jnp.where(
            dst >= 0, _paged_rows(pages, jnp.maximum(dst, 0), ps, R), R
        )
        new_cache["pk"] = ck.at[dst_rows].set(ck[src_rows], mode="drop")
        new_cache["pv"] = cv.at[dst_rows].set(cv[src_rows], mode="drop")
        if "pks" in cache:
            # scales are page metadata: the accepted rows' scale control
            # words move with the int8 payload, same gather/scatter maps
            for n in ("pks", "pvs"):
                new_cache[n] = cache[n].at[dst_rows].set(cache[n][src_rows], mode="drop")
        return new_cache
    ck, cv = cache["k"], cache["v"]
    B, W = ck.shape[0], ck.shape[1]
    bidx = jnp.arange(B)[:, None]
    src_slot = jnp.remainder(jnp.maximum(src, 0), W)
    dst_slot = jnp.where(dst >= 0, jnp.remainder(dst, W), W)
    new_cache["k"] = ck.at[bidx, dst_slot].set(ck[bidx, src_slot], mode="drop")
    new_cache["v"] = cv.at[bidx, dst_slot].set(cv[bidx, src_slot], mode="drop")
    if "ks" in cache:
        for n in ("ks", "vs"):
            new_cache[n] = cache[n].at[bidx, dst_slot].set(cache[n][bidx, src_slot], mode="drop")
    return new_cache


def cow_copy_page(cache: Params, old_page: int, new_page: int, page_size: int) -> Params:
    """Copy-on-write page duplication: after
    :meth:`repro.core.pages.PageTable.ensure_writable` rebinds a shared page,
    copy the old physical page's rows into the fresh one — the int8 payload
    AND the per-row scale leaves together.  A page is only meaningful as the
    (int8 rows, scale rows) pair: copying pk/pv but aliasing pks/pvs would
    let the writer's next row write corrupt the sibling branch still reading
    the shared page's scales.
    """
    o0, n0 = int(old_page) * page_size, int(new_page) * page_size

    def fix(part, stacked):
        def f(kp, leaf):
            name = getattr(kp[-1], "key", None)
            if name not in ("pk", "pv", "pks", "pvs"):
                return leaf
            if stacked:  # scan-stacked: superblock axis leads
                return leaf.at[:, n0 : n0 + page_size].set(leaf[:, o0 : o0 + page_size])
            return leaf.at[n0 : n0 + page_size].set(leaf[o0 : o0 + page_size])

        return jax.tree_util.tree_map_with_path(f, part)

    return {"scan": fix(cache["scan"], True), "rest": fix(cache["rest"], False)}


def _decode_attn_paged_spec(
    xn: jnp.ndarray,  # (B, T, d)
    p: Params,
    cfg: ModelConfig,
    cache: Params,
    lengths: jnp.ndarray,  # (B,)
    pages: jnp.ndarray,    # (B, max_pages) int32 block table
) -> Tuple[jnp.ndarray, Params]:
    """Paged twin of :func:`_decode_attn_prefix_spec`: same per-token valid
    prefixes, but KV rows live in the flat page pool and every access goes
    through the block table.  With the identity table this is bitwise-equal
    to the contiguous path (the gather view IS the contiguous buffer)."""
    B, T, _ = xn.shape
    ps = cfg.page_size
    R = cache["pk"].shape[0]
    pos = _spec_positions(lengths, T)
    q, k, v = L._qkv(xn, p, cfg, pos)
    rows = _paged_rows(pages, pos, ps, R)
    cks = cvs = None
    if "pks" in cache:
        k, v, ksr, vsr = _quant_kv_rows(k, v)
        cks = cache["pks"].at[rows].set(ksr, mode="drop")
        cvs = cache["pvs"].at[rows].set(vsr, mode="drop")
    ck = cache["pk"].at[rows].set(k.astype(cache["pk"].dtype), mode="drop")
    cv = cache["pv"].at[rows].set(v.astype(cache["pv"].dtype), mode="drop")
    if use_kernels(cfg):
        from repro.kernels.flash_attention import flash_decode_paged

        scl = None if cks is None else jnp.stack([cks, cvs])
        out = flash_decode_paged(q, ck, cv, pos, pages, page_size=ps, scales=scl)
    else:
        Smax = pages.shape[1] * ps
        hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
        groups = cfg.num_heads // nkv
        vk = _paged_view(_deq(ck, cks), pages, ps)  # (B, Smax, nkv, hd)
        vv = _paged_view(_deq(cv, cvs), pages, ps)
        mapped = jnp.repeat(pages >= 0, ps, axis=1)  # (B, Smax)
        valid = mapped[:, None, :] & (
            jnp.arange(Smax)[None, None, :] <= pos[:, :, None]
        )  # (B, T, Smax)
        scale = 1.0 / math.sqrt(hd)
        qg = q.reshape(B, T, nkv, groups, hd)
        s = jnp.einsum("btngh,bsnh->bngts", qg.astype(jnp.float32), vk.astype(jnp.float32)) * scale
        s = jnp.where(valid[:, None, None, :, :], s, L.NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bngts,bsnh->btngh", w, vv.astype(jnp.float32))
        out = out.reshape(B, T, cfg.num_heads, hd).astype(xn.dtype)
    y = jnp.einsum("btnh,nhd->btd", out, p["wo"].astype(out.dtype))
    nc = {"pk": ck, "pv": cv}
    if cks is not None:
        nc["pks"], nc["pvs"] = cks, cvs
    return y, nc


def _decode_attn_paged_tree(
    xn: jnp.ndarray,  # (B, T, d) — T draft-tree nodes per sequence
    p: Params,
    cfg: ModelConfig,
    cache: Params,
    lengths: jnp.ndarray,  # (B,) committed-prefix length per sequence
    tree: "TreePlan",
    pages: jnp.ndarray,    # (B, max_pages) int32 block table
) -> Tuple[jnp.ndarray, Params]:
    """Paged twin of :func:`_decode_attn_prefix_tree`: node t occupies
    LOGICAL row ``lengths[b] + t`` (physical row via the block table) at
    rotary position ``lengths[b] + depth(t)``; the ancestor mask operates on
    logical rows so the physical layout never leaks into the math."""
    B, T, _ = xn.shape
    ps = cfg.page_size
    R = cache["pk"].shape[0]
    depths = jnp.asarray(tree.depths(), jnp.int32)
    lrows = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B, T)
    pos = lengths[:, None] + depths[None, :]  # rotary positions
    q, k, v = L._qkv(xn, p, cfg, pos)
    rows = _paged_rows(pages, lrows, ps, R)
    cks = cvs = None
    if "pks" in cache:
        k, v, ksr, vsr = _quant_kv_rows(k, v)
        cks = cache["pks"].at[rows].set(ksr, mode="drop")
        cvs = cache["pvs"].at[rows].set(vsr, mode="drop")
    ck = cache["pk"].at[rows].set(k.astype(cache["pk"].dtype), mode="drop")
    cv = cache["pv"].at[rows].set(v.astype(cache["pv"].dtype), mode="drop")
    if use_kernels(cfg):
        from repro.kernels.flash_attention import flash_decode_paged

        scl = None if cks is None else jnp.stack([cks, cvs])
        out = flash_decode_paged(
            q, ck, cv, lengths, pages, page_size=ps,
            ancestors=jnp.asarray(tree.ancestor_words(), jnp.int32),
            base=lengths, scales=scl,
        )
    else:
        Smax = pages.shape[1] * ps
        hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
        groups = cfg.num_heads // nkv
        vk = _paged_view(_deq(ck, cks), pages, ps)
        vv = _paged_view(_deq(cv, cvs), pages, ps)
        mapped = jnp.repeat(pages >= 0, ps, axis=1)  # (B, Smax)
        table = jnp.asarray(tree.ancestor_table(), bool)  # (T, T)
        u = jnp.arange(Smax)[None, :] - lengths[:, None]  # (B, Smax) draft-row index
        in_draft = (u >= 0) & (u < T)
        anc_ok = table[:, jnp.clip(u, 0, T - 1)]  # (T, B, Smax)
        valid = mapped[:, None, :] & (
            (u < 0)[:, None, :]
            | (in_draft[:, None, :] & jnp.transpose(anc_ok, (1, 0, 2)))
        )  # (B, T, Smax)
        scale = 1.0 / math.sqrt(hd)
        qg = q.reshape(B, T, nkv, groups, hd)
        s = jnp.einsum("btngh,bsnh->bngts", qg.astype(jnp.float32), vk.astype(jnp.float32)) * scale
        s = jnp.where(valid[:, None, None, :, :], s, L.NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bngts,bsnh->btngh", w, vv.astype(jnp.float32))
        out = out.reshape(B, T, cfg.num_heads, hd).astype(xn.dtype)
    y = jnp.einsum("btnh,nhd->btd", out, p["wo"].astype(out.dtype))
    nc = {"pk": ck, "pv": cv}
    if cks is not None:
        nc["pks"], nc["pvs"] = cks, cvs
    return y, nc


def _decode_attn_rolling_tree(
    xn: jnp.ndarray,  # (B, T, d) — T draft-tree nodes per sequence
    p: Params,
    cfg: ModelConfig,
    cache: Params,
    lengths: jnp.ndarray,  # (B,) committed-prefix length per sequence
    window: int,
    tree: "TreePlan",
) -> Tuple[jnp.ndarray, Params]:
    """Ancestor-masked tree attention against a rolling (modulo) KV cache.

    Node t lands at slot ``(lengths[b] + t) % W`` with rotary position
    ``lengths[b] + depth(t)``.  Validity combines three predicates: the slot
    must hold a written row (abs_pos >= 0), the row must be inside the
    node's window measured in SEQUENTIAL positions (an ancestor's sequential
    position is ``lengths + depth``, not its row index — using row indices
    would widen the window for deep trees), and draft rows must be on the
    node's root path.  The accepted path's row moves arrive NEXT launch as
    fused commit maps (mod W) — this is what un-bans branchy trees on
    rolling layers under the paged plane.
    """
    B, T, _ = xn.shape
    W = cache["k"].shape[1]
    assert T <= W, "draft tree must not exceed the rolling window"
    depths = jnp.asarray(tree.depths(), jnp.int32)
    lrows = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # (B, T)
    pos = lengths[:, None] + depths[None, :]  # rotary / sequential positions
    q, k, v = L._qkv(xn, p, cfg, pos)
    bidx = jnp.arange(B)[:, None]
    slots = jnp.remainder(lrows, W)
    cks = cvs = None
    if "ks" in cache:
        k, v, ksr, vsr = _quant_kv_rows(k, v)
        cks = cache["ks"].at[bidx, slots].set(ksr)
        cvs = cache["vs"].at[bidx, slots].set(vsr)
    ck = cache["k"].at[bidx, slots].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[bidx, slots].set(v.astype(cache["v"].dtype))
    limit = min(window, W) if window else W
    hd, nkv = cfg.resolved_head_dim, cfg.num_kv_heads
    groups = cfg.num_heads // nkv
    head = lengths + T - 1  # (B,) last written row's absolute index
    slot = jnp.arange(W)
    write = jnp.remainder(head, W)
    abs_pos = head[:, None] - jnp.remainder(write[:, None] - slot[None, :], W)  # (B, W)
    u = abs_pos - lengths[:, None]  # draft-row index of each slot (>= 0 iff draft)
    in_draft = (u >= 0) & (u < T)
    table = jnp.asarray(tree.ancestor_table(), bool)  # (T, T)
    anc_ok = jnp.transpose(table[:, jnp.clip(u, 0, T - 1)], (1, 0, 2))  # (B, T, W)
    # window cut on sequential positions: committed rows sit at their row
    # index; a draft row's sequential position (if accepted) is its depth
    eff = jnp.where(in_draft, lengths[:, None] + depths[jnp.clip(u, 0, T - 1)], abs_pos)
    valid = (
        (abs_pos >= 0)[:, None, :]
        & (eff[:, None, :] > pos[:, :, None] - limit)
        & ((u < 0)[:, None, :] | (in_draft[:, None, :] & anc_ok))
    )  # (B, T, W)
    scale = 1.0 / math.sqrt(hd)
    qg = q.reshape(B, T, nkv, groups, hd)
    ckf, cvf = _deq(ck, cks), _deq(cv, cvs)
    s = jnp.einsum("btngh,bsnh->bngts", qg.astype(jnp.float32), ckf.astype(jnp.float32)) * scale
    s = jnp.where(valid[:, None, None, :, :], s, L.NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngts,bsnh->btngh", w, cvf.astype(jnp.float32))
    out = out.reshape(B, T, cfg.num_heads, hd).astype(xn.dtype)
    y = jnp.einsum("btnh,nhd->btd", out, p["wo"].astype(out.dtype))
    nc = {"k": ck, "v": cv}
    if cks is not None:
        nc["ks"], nc["vs"] = cks, cvs
    return y, nc


def _decode_attn_rolling(
    xn: jnp.ndarray,
    p: Params,
    cfg: ModelConfig,
    cache: Params,
    cache_index: jnp.ndarray,
    window: int,
) -> Tuple[jnp.ndarray, Params]:
    """One-token attention against a (possibly rolling-window) KV cache."""
    B = xn.shape[0]
    W = cache["k"].shape[1]
    positions = jnp.broadcast_to(cache_index, (B, 1)).astype(jnp.int32)
    q, k, v = L._qkv(xn, p, cfg, positions)
    write = jnp.remainder(cache_index, W)
    cks = cvs = None
    if "ks" in cache:
        k, v, ksr, vsr = _quant_kv_rows(k, v)
        cks = jax.lax.dynamic_update_slice_in_dim(cache["ks"], ksr, write, axis=1)
        cvs = jax.lax.dynamic_update_slice_in_dim(cache["vs"], vsr, write, axis=1)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), write, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), write, axis=1)
    nc = {"k": ck, "v": cv}
    if cks is not None:
        nc["ks"], nc["vs"] = cks, cvs
    # validity: slot position must be within [cache_index - limit + 1, cache_index]
    limit = min(window, W) if window else W
    if cfg.decode_plane and use_kernels(cfg) and window:
        # window-steered flash-decode: the rolling cache's wrap point rides
        # the scalar-prefetch path; at most W KV bytes move per head
        from repro.kernels.flash_attention import flash_decode_window

        scl = None if cks is None else jnp.stack([cks, cvs])
        out = flash_decode_window(
            q, ck, cv, jnp.broadcast_to(cache_index, (B,)).astype(jnp.int32),
            window=limit, scales=scl,
        )
        y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(out.dtype))
        return y, nc
    slot = jnp.arange(W)
    # absolute position stored in slot s (rolling): the largest p <= cache_index with p % W == s
    offset = jnp.remainder(write - slot, W)
    abs_pos = cache_index - offset
    valid = (abs_pos >= 0) & (abs_pos > cache_index - limit)
    scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
    groups = cfg.num_heads // cfg.num_kv_heads
    ckf, cvf = _deq(ck, cks), _deq(cv, cvs)
    qg = q.reshape(B, 1, cfg.num_kv_heads, groups, cfg.resolved_head_dim)
    s = jnp.einsum("bsngh,btnh->bngst", qg.astype(jnp.float32), ckf.astype(jnp.float32)) * scale
    s = jnp.where(valid[None, None, None, None, :], s, L.NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bngst,btnh->bsngh", w, cvf.astype(jnp.float32))
    out = out.reshape(B, 1, cfg.num_heads, cfg.resolved_head_dim).astype(xn.dtype)
    y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(out.dtype))
    return y, nc


def _decode_attn_prefix(
    xn: jnp.ndarray,
    p: Params,
    cfg: ModelConfig,
    cache: Params,
    cache_index: jnp.ndarray,
) -> Tuple[jnp.ndarray, Params]:
    """One-token attention over the valid cache prefix [0, cache_index].

    The decode-plane attention path for full-attention layers (non-rolling
    caches: slot position == absolute position).  Whenever kernels run
    (always on TPU) this is the length-steered flash-decode kernel — the
    cache length rides the scalar-prefetch path and only the valid prefix's
    KV blocks are ever DMA'd (:mod:`repro.kernels.flash_attention.decode`);
    off-TPU the same prefix semantics run as masked jnp.
    """
    B = xn.shape[0]
    positions = jnp.broadcast_to(cache_index, (B, 1)).astype(jnp.int32)
    q, k, v = L._qkv(xn, p, cfg, positions)
    cks = cvs = None
    if "ks" in cache:
        k, v, ksr, vsr = _quant_kv_rows(k, v)
        cks = jax.lax.dynamic_update_slice_in_dim(cache["ks"], ksr, cache_index, axis=1)
        cvs = jax.lax.dynamic_update_slice_in_dim(cache["vs"], vsr, cache_index, axis=1)
    ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
    if use_kernels(cfg):
        from repro.kernels.flash_attention import flash_decode

        scl = None if cks is None else jnp.stack([cks, cvs])
        out = flash_decode(q, ck, cv, cache_index, scales=scl)
    else:
        S = ck.shape[1]
        valid = jnp.arange(S) <= cache_index
        scale = 1.0 / math.sqrt(cfg.resolved_head_dim)
        groups = cfg.num_heads // cfg.num_kv_heads
        ckf, cvf = _deq(ck, cks), _deq(cv, cvs)
        qg = q.reshape(B, 1, cfg.num_kv_heads, groups, cfg.resolved_head_dim)
        s = jnp.einsum("bsngh,btnh->bngst", qg.astype(jnp.float32), ckf.astype(jnp.float32)) * scale
        s = jnp.where(valid[None, None, None, None, :], s, L.NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bngst,btnh->bsngh", w, cvf.astype(jnp.float32))
        out = out.reshape(B, 1, cfg.num_heads, cfg.resolved_head_dim).astype(xn.dtype)
    y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(out.dtype))
    nc = {"k": ck, "v": cv}
    if cks is not None:
        nc["ks"], nc["vs"] = cks, cvs
    return y, nc
