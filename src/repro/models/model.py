"""Unified Model facade: init / train loss / prefill / decode for every
assigned architecture, with scan-over-superblocks and injectable MoE apply
(so the distributed runtime can substitute sharded expert parallelism).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCell
from repro.models import layers as L
from repro.models import moe
from repro.models import transformer as T

Params = Dict[str, Any]


class Model:
    def __init__(
        self,
        cfg: ModelConfig,
        moe_apply: Optional[T.MoeApply] = None,
        constrain: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
        decode_moe_apply: Optional[T.DecodeApply] = None,
    ):
        self.cfg = cfg
        self.moe_apply = moe_apply or T._default_moe_apply(cfg)
        # Decode-plane plan executor: the distributed runtime injects the
        # shard_map psum strategy (each shard runs only its resident experts
        # for the cache-carried plan's rows, one psum combines) — see
        # launch.steps.build_model.  Default: the single-host data plane.
        self.decode_moe_apply = decode_moe_apply or moe.moe_decode_ffn
        # Residual-stream sharding constraint injected by the distributed
        # runtime (launch.steps): pins the post-embedding activations to
        # (batch-sharded, replicated-over-model).  Without it, a d-sharded
        # embedding table propagates a d-sharded residual through the
        # optimization barriers and every projection all-gathers its input
        # (perf iteration B-6, EXPERIMENTS.md §Perf).
        self.constrain = constrain or (lambda x: x)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def init(self, key) -> Params:
        return T.init_params(key, self.cfg)

    def init_cache(self, batch: int, max_len: int, *, shardings: Optional[Any] = None) -> Params:
        """Fresh decode cache; with ``shardings`` (a pytree of NamedShardings
        matching the cache structure) the zeros are allocated directly with
        the requested layout on the mesh — no host-side build + device_put
        round trip, which matters when the KV cache is the largest live
        buffer of the serving process."""
        if shardings is None:
            return T.init_cache(self.cfg, batch, max_len)
        return jax.jit(
            partial(T.init_cache, self.cfg, batch, max_len), out_shardings=shardings
        )()

    # ------------------------------------------------------------------
    # embedding / stack plumbing
    # ------------------------------------------------------------------
    def _embed(self, params: Params, tokens: jnp.ndarray, frontend: Optional[jnp.ndarray]):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = L.embed(tokens, params["embed"], dtype)
        if cfg.frontend and frontend is not None:
            F = frontend.shape[1]
            fx = jnp.einsum("bfe,ed->bfd", frontend.astype(dtype), params["frontend"]["proj"].astype(dtype))
            x = jnp.concatenate([fx, x[:, F:]], axis=1)
        return self.constrain(x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(dtype))

    def _pattern(self) -> Tuple[Tuple[str, ...], int, int]:
        pat = self.cfg.block_pattern
        n_sb, n_rest = divmod(self.cfg.num_layers, len(pat))
        return pat, n_sb, n_rest

    # ------------------------------------------------------------------
    # train forward
    # ------------------------------------------------------------------
    def stack_train(self, params: Params, x: jnp.ndarray, positions: jnp.ndarray):
        cfg = self.cfg
        pat, n_sb, n_rest = self._pattern()
        route_src = x  # layer-0 control-plane source = embeddings

        def sb_fn(carry, p_sb):
            h, rs = carry
            aux = jnp.zeros((2,), jnp.float32)
            for j, kind in enumerate(pat):
                h, rs, a = T.apply_layer_train(h, rs, p_sb[f"b{j}"], kind, cfg, positions, self.moe_apply)
                aux = aux + a
            return (h, rs), aux

        f = jax.checkpoint(sb_fn) if cfg.remat else sb_fn
        aux_total = jnp.zeros((2,), jnp.float32)
        if n_sb:
            (x, route_src), auxs = jax.lax.scan(f, (x, route_src), params["blocks"]["scan"])
            aux_total = aux_total + auxs.sum(axis=0)
        kinds = cfg.layer_kinds
        for j, p in enumerate(params["blocks"]["rest"]):
            kind = kinds[n_sb * len(pat) + j]
            x, route_src, a = T.apply_layer_train(x, route_src, p, kind, cfg, positions, self.moe_apply)
            aux_total = aux_total + a
        return x, aux_total

    def logits(self, params: Params, x: jnp.ndarray) -> jnp.ndarray:
        x = L.rms_norm(x, params["final_norm"])
        table = params["embed"] if self.cfg.tie_embeddings else params["unembed"]
        return L.unembed(x, table)

    def forward(
        self,
        params: Params,
        tokens: jnp.ndarray,  # (B, S)
        frontend: Optional[jnp.ndarray] = None,  # (B, F, fd)
    ):
        """Non-cached full forward: (logits (B, S, V) f32, aux losses (2,))."""
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = self._embed(params, tokens, frontend)
        x, aux = self.stack_train(params, x, positions)
        return self.logits(params, x), aux

    def forward_train(
        self,
        params: Params,
        tokens: jnp.ndarray,  # (B, S)
        frontend: Optional[jnp.ndarray] = None,  # (B, F, fd)
        *,
        lb_coef: float = 0.01,
        z_coef: float = 1e-4,
    ):
        """Next-token cross-entropy over the backbone; frontend positions masked."""
        cfg = self.cfg
        B, S = tokens.shape
        logits, aux = self.forward(params, tokens, frontend)

        targets = tokens[:, 1:]
        lse = jax.nn.logsumexp(logits[:, :-1], axis=-1)
        tgt_logit = jnp.take_along_axis(logits[:, :-1], targets[..., None], axis=-1)[..., 0]
        nll = lse - tgt_logit  # (B, S-1)
        F = cfg.frontend_tokens if cfg.frontend else 0
        mask = (jnp.arange(S - 1) >= F).astype(jnp.float32)[None, :]
        denom = jnp.maximum(mask.sum() * B, 1.0)
        ce = (nll * mask).sum() / denom
        n_moe = max(sum(1 for k in cfg.layer_kinds if k == "moe"), 1)
        loss = ce + lb_coef * aux[0] / n_moe + z_coef * aux[1] / n_moe
        metrics = {"loss": loss, "ce": ce, "lb_loss": aux[0] / n_moe, "z_loss": aux[1] / n_moe}
        return loss, metrics

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    def prefill(
        self,
        params: Params,
        tokens: jnp.ndarray,  # (B, S)
        cache: Params,
        frontend: Optional[jnp.ndarray] = None,
    ):
        """Fill the cache with the prompt; return (last-position logits, cache)."""
        cfg = self.cfg
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        x = self._embed(params, tokens, frontend)
        pat, n_sb, n_rest = self._pattern()
        route_src = x

        def sb_fn(carry, xs):
            h, rs = carry
            p_sb, c_sb = xs
            aux = jnp.zeros((2,), jnp.float32)
            new_c = {}
            for j, kind in enumerate(pat):
                h, rs, nc, a = T.apply_layer_prefill(
                    h, rs, p_sb[f"b{j}"], c_sb[f"b{j}"], kind, cfg, positions, self.moe_apply
                )
                new_c[f"b{j}"] = nc
                aux = aux + a
            return (h, rs), new_c

        new_cache: Params = {"scan": {}, "rest": []}
        if n_sb:
            (x, route_src), new_scan = jax.lax.scan(
                sb_fn, (x, route_src), (params["blocks"]["scan"], cache["scan"])
            )
            new_cache["scan"] = new_scan
        kinds = cfg.layer_kinds
        for j, (p, c) in enumerate(zip(params["blocks"]["rest"], cache["rest"])):
            kind = kinds[n_sb * len(pat) + j]
            x, route_src, nc, _ = T.apply_layer_prefill(x, route_src, p, c, kind, cfg, positions, self.moe_apply)
            new_cache["rest"].append(nc)
        last = self.logits(params, x[:, -1:, :])[:, 0]  # (B, V)
        return last, new_cache

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def decode_step(
        self,
        params: Params,
        cache: Params,
        tokens: jnp.ndarray,  # (B,) int32 — last generated token
        cache_index: jnp.ndarray,  # scalar int32 — number of tokens already in cache
    ):
        """One serve step: logits for the next token + updated cache."""
        cfg = self.cfg
        B = tokens.shape[0]
        x = L.embed(tokens[:, None], params["embed"], jnp.dtype(cfg.dtype))
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
        pat, n_sb, n_rest = self._pattern()
        route_src = x

        def sb_fn(carry, xs):
            h, rs = carry
            p_sb, c_sb = xs
            new_c = {}
            for j, kind in enumerate(pat):
                h, rs, nc, _ = T.apply_layer_decode(
                    h, rs, p_sb[f"b{j}"], c_sb[f"b{j}"], kind, cfg, cache_index,
                    self.moe_apply, self.decode_moe_apply,
                )
                new_c[f"b{j}"] = nc
            return (h, rs), new_c

        new_cache: Params = {"scan": {}, "rest": []}
        if n_sb:
            (x, route_src), new_scan = jax.lax.scan(
                sb_fn, (x, route_src), (params["blocks"]["scan"], cache["scan"])
            )
            new_cache["scan"] = new_scan
        kinds = cfg.layer_kinds
        for j, (p, c) in enumerate(zip(params["blocks"]["rest"], cache["rest"])):
            kind = kinds[n_sb * len(pat) + j]
            x, route_src, nc, _ = T.apply_layer_decode(
                x, route_src, p, c, kind, cfg, cache_index,
                self.moe_apply, self.decode_moe_apply,
            )
            new_cache["rest"].append(nc)
        logits = self.logits(params, x)[:, 0]  # (B, V)
        return logits, new_cache

    # ------------------------------------------------------------------
    # speculative / ragged multi-token decode
    # ------------------------------------------------------------------
    def decode_tokens(
        self,
        params: Params,
        cache: Params,
        tokens: jnp.ndarray,  # (B, T) — last accepted token + T-1 draft tokens
        lengths: jnp.ndarray,  # (B,) int32 — per-sequence tokens already in cache
        prev_accept: Optional[jnp.ndarray] = None,  # (B,) int32 plan-row select
        *,
        telemetry: bool = False,
        tree: Optional[Any] = None,  # core.plans.TreePlan — draft-tree topology
        pages: Optional[jnp.ndarray] = None,  # (B, max_pages) int32 block tables
        commit: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,  # (dst, src)
    ):
        """One speculative serve launch: T tokens per sequence, ragged batch.

        Token (b, t) sits at absolute position ``lengths[b] + t``; the
        returned logits (B, T, V) score the successor of each position, so a
        greedy verifier accepts the draft prefix that matches
        ``argmax(logits[:, :-1])`` (see launch/serve.py).  ``prev_accept``
        selects, per sequence, the cached plan row computed from the route
        source of the position the PREVIOUS launch's verification accepted —
        this is what makes speculative decode bitwise-faithful to sequential
        decode under rollback.  With ``telemetry=True`` also returns a
        metrics dict carrying the mean stale-vs-fresh plan top-k agreement.

        With ``tree`` (a static :class:`~repro.core.plans.TreePlan` with
        ``num_nodes == T``) the T tokens form a draft tree: node t rides
        cache row ``lengths[b] + t``, attends through the tree's ancestor
        mask, and ``logits[:, t]`` scores the successor of node t given its
        root-path context.  The verifier walks the tree
        (:func:`repro.launch.speculative.greedy_accept_tree`), then
        :meth:`commit_tree_path` compacts the accepted path's cache rows;
        ``prev_accept`` is then the accepted NODE INDEX (for a chain this is
        the accepted-count-minus-one of the linear path — same number).

        Paged caches (``cfg.paged``) take two more control words: ``pages``,
        the per-slot block table steering every KV access through
        logical→physical translation, and ``commit``, the PREVIOUS verify
        round's accepted-path row moves ``(dst, src)`` in logical positions
        (-1 = no-op) which are applied at the top of each layer before its
        new writes — tree commit fused into the decode launch, so the paged
        tree path issues ZERO standalone commit launches (full pages were
        rewired on the host; only boundary-page rows move here).
        """
        cfg = self.cfg
        B = tokens.shape[0]
        if tree is not None and tree.num_nodes != tokens.shape[1]:
            raise ValueError(
                f"tree has {tree.num_nodes} nodes but the launch carries "
                f"{tokens.shape[1]} tokens"
            )
        if prev_accept is None:
            prev_accept = jnp.zeros((B,), jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32).reshape(B)
        x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
        pat, n_sb, n_rest = self._pattern()
        route_src = x
        agree_sum = jnp.float32(0.0)
        n_moe = max(sum(1 for k in cfg.layer_kinds if k == "moe"), 1)

        def sb_fn(carry, xs):
            h, rs, agg = carry
            p_sb, c_sb = xs
            new_c = {}
            for j, kind in enumerate(pat):
                h, rs, nc, a = T.apply_layer_decode_spec(
                    h, rs, p_sb[f"b{j}"], c_sb[f"b{j}"], kind, cfg,
                    lengths, prev_accept, self.moe_apply,
                    decode_apply=self.decode_moe_apply, telemetry=telemetry,
                    tree=tree, pages=pages, commit=commit,
                )
                new_c[f"b{j}"] = nc
                agg = agg + a
            return (h, rs, agg), new_c

        new_cache: Params = {"scan": {}, "rest": []}
        if n_sb:
            (x, route_src, agree_sum), new_scan = jax.lax.scan(
                sb_fn, (x, route_src, agree_sum), (params["blocks"]["scan"], cache["scan"])
            )
            new_cache["scan"] = new_scan
        kinds = cfg.layer_kinds
        for j, (p, c) in enumerate(zip(params["blocks"]["rest"], cache["rest"])):
            kind = kinds[n_sb * len(pat) + j]
            x, route_src, nc, a = T.apply_layer_decode_spec(
                x, route_src, p, c, kind, cfg, lengths, prev_accept,
                self.moe_apply, decode_apply=self.decode_moe_apply,
                telemetry=telemetry, tree=tree, pages=pages, commit=commit,
            )
            new_cache["rest"].append(nc)
            agree_sum = agree_sum + a
        logits = self.logits(params, x)  # (B, T, V)
        if telemetry:
            return logits, new_cache, {"plan_agreement": agree_sum / n_moe}
        return logits, new_cache

    # ------------------------------------------------------------------
    # continuous-batching cache surgery
    # ------------------------------------------------------------------
    def write_cache_slot(self, cache: Params, one_cache: Params, slot) -> Params:
        """Admit a freshly-prefilled single-sequence cache into batch ``slot``.

        ``one_cache`` must come from ``init_cache(1, max_len)`` + ``prefill``
        of the admitted prompt; scan-stacked leaves carry batch on axis 1
        (axis 0 is the superblock stack), rest leaves on axis 0.
        """

        def at_axis(axis):
            def write(f, o):
                return jax.lax.dynamic_update_slice_in_dim(
                    f, o.astype(f.dtype), slot, axis=axis
                )

            return write

        return {
            "scan": jax.tree.map(at_axis(1), cache["scan"], one_cache["scan"]),
            "rest": jax.tree.map(at_axis(0), cache["rest"], one_cache["rest"]),
        }

    def write_cache_slot_paged(self, cache: Params, one_cache: Params, slot, rows) -> Params:
        """Paged admission: page assignment + scatter, never a stripe copy.

        ``one_cache`` comes from a CONTIGUOUS B=1 prefill (build the prefill
        model with ``paged=False``); ``rows`` is the (max_len,) int32 vector
        of physical pool rows backing each logical prompt position — entries
        at/above the pool size are dropped, which is how trie-shared pages
        (and positions past the prompt) skip the copy entirely: admitting a
        fully trie-resident prompt moves ZERO KV bytes, the block table just
        adopts the shared pages on the host.  Non-pool leaves (DecodePlans,
        rolling-window buffers) are per-slot and write at batch ``slot``
        exactly as :meth:`write_cache_slot` does.
        """
        rows = jnp.asarray(rows, jnp.int32)

        def conv(dest, src, axis):
            if isinstance(dest, dict):
                out = {}
                for name, d in dest.items():
                    if name in ("pk", "pv", "pks", "pvs"):
                        # the contiguous prefill leaf (k/v, or its ks/vs
                        # per-token scale row — quantized at prefill, the
                        # scales scatter through the SAME row map so trie
                        # hits adopt quantized pages + scales zero-copy)
                        s = src[name[1:]]
                        if axis == 1:  # scan-stacked: superblock axis leads
                            out[name] = d.at[:, rows].set(
                                s[:, 0].astype(d.dtype), mode="drop"
                            )
                        else:
                            out[name] = d.at[rows].set(
                                s[0].astype(d.dtype), mode="drop"
                            )
                    else:
                        out[name] = conv(d, src[name], axis)
                return out
            if isinstance(dest, list):
                return [conv(d, s, axis) for d, s in zip(dest, src)]
            return jax.lax.dynamic_update_slice_in_dim(
                dest, src.astype(dest.dtype), slot, axis=axis
            )

        return {
            "scan": conv(cache["scan"], one_cache["scan"], 1),
            "rest": conv(cache["rest"], one_cache["rest"], 0),
        }

    def paginate_cache(self, cache: Params, max_len: int) -> Params:
        """Re-layout a contiguous cache into the paged pool layout.

        Benchmark/test plumbing for the bitwise-parity contract: with the
        identity block table (:func:`repro.models.transformer.identity_page_table`)
        slot ``b``'s logical position ``pos`` lands at pool row
        ``b * max_pages * page_size + pos`` — exactly the flattened contiguous
        buffer — so the paged chain path must be bitwise-equal to the
        contiguous path on the converted cache.  Rolling-window leaves (and
        rec/ssm states, plans) pass through untouched, mirroring
        ``init_layer_cache``.
        """
        cfg = self.cfg
        pat, n_sb, n_rest = self._pattern()
        kinds = cfg.layer_kinds
        ps, mp = cfg.page_size, T.max_pages_for(cfg, max_len)

        def conv_layer(c, kind, stacked):
            window = cfg.local_window if (kind == "local" or cfg.attention_kind == "local") else 0
            if kind not in ("attn", "local", "moe") or window:
                return c
            out = dict(c)
            for name, pname in (("k", "pk"), ("v", "pv")):
                leaf = out.pop(name)
                pad = mp * ps - leaf.shape[-3]
                if pad:
                    cfgpad = [(0, 0)] * leaf.ndim
                    cfgpad[-3] = (0, pad)
                    leaf = jnp.pad(leaf, cfgpad)
                nkv, hd = leaf.shape[-2:]
                lead = leaf.shape[:-4]  # () or (n_sb,)
                out[pname] = leaf.reshape(*lead, -1, nkv, hd)
            for name, pname in (("ks", "pks"), ("vs", "pvs")):
                if name not in out:
                    continue
                # per-token scale rows flatten to pool-row order alongside
                # their int8 pages; pad with ONES (the init value — padded
                # rows are masked but a 0 scale would zero a real row if the
                # pool were ever compacted over it)
                leaf = out.pop(name)
                pad = mp * ps - leaf.shape[-1]
                if pad:
                    cfgpad = [(0, 0)] * leaf.ndim
                    cfgpad[-1] = (0, pad)
                    leaf = jnp.pad(leaf, cfgpad, constant_values=1.0)
                lead = leaf.shape[:-2]  # () or (n_sb,)
                out[pname] = leaf.reshape(*lead, -1)
            return out

        scan = (
            {f"b{j}": conv_layer(cache["scan"][f"b{j}"], pat[j], True) for j in range(len(pat))}
            if n_sb
            else {}
        )
        rest = [
            conv_layer(c, kinds[n_sb * len(pat) + j], False)
            for j, c in enumerate(cache["rest"])
        ]
        return {"scan": scan, "rest": rest}

    def commit_tree_path(self, cache: Params, lengths, path) -> Params:
        """Compact an accepted draft-tree root path into contiguous cache rows.

        After tree verification, the accepted nodes ``path[b] = (0, u_1, ...,
        u_{a-1})`` sit at scattered rows ``lengths[b] + u_i``; the next launch
        treats ``[0, lengths[b] + a)`` as committed prefix, so row
        ``lengths[b] + i`` must hold node ``u_i``'s KV.  ``path`` is (B, T)
        int32, padded with the identity (``path[b, i] = i`` for i >= the
        accepted count) so the pad writes copy rows onto themselves — a
        parked or fully-chain-accepted slot is a bitwise no-op.  Only KV
        leaves move; plan rows stay node-indexed (``prev_accept`` selects the
        accepted node's row directly) and rejected rows are overwritten by
        the next launch, exactly like linear rollback.

        This standalone launch serves the LEGACY contiguous path only.  Paged
        caches never call it: full pages are rewired in the host block table
        and boundary-page row moves ride the next decode launch as fused
        ``commit`` maps (see :func:`repro.core.pages.commit_maps`).
        """
        lengths = jnp.asarray(lengths, jnp.int32)
        path = jnp.asarray(path, jnp.int32)
        T_ = path.shape[1]
        dst = lengths[:, None] + jnp.arange(T_, dtype=jnp.int32)[None, :]
        src = lengths[:, None] + path

        def gather_rows(leaf, batch_axis):
            B = leaf.shape[batch_axis]
            bidx = jnp.arange(B)[:, None]
            if batch_axis == 0:
                return leaf.at[bidx, dst].set(leaf[bidx, src])
            return leaf.at[:, bidx, dst].set(leaf[:, bidx, src])

        def fix(part, batch_axis):
            def f(kp, leaf):
                name = getattr(kp[-1], "key", None)
                # ks/vs: the per-token scale control words move with their
                # int8 rows — an accepted node's row is only meaningful as
                # the (int8 payload, scale) pair
                return gather_rows(leaf, batch_axis) if name in ("k", "v", "ks", "vs") else leaf

            return jax.tree_util.tree_map_with_path(f, part)

        return {"scan": fix(cache["scan"], 1), "rest": fix(cache["rest"], 0)}


# ---------------------------------------------------------------------------
# abstract input specs (dry-run stand-ins; no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell."""
    B, S = cell.global_batch, cell.seq_len
    sds = jax.ShapeDtypeStruct
    specs: Dict[str, Any] = {}
    if cell.step in ("train", "prefill"):
        specs["tokens"] = sds((B, S), jnp.int32)
        if cfg.frontend:
            specs["frontend"] = sds((B, cfg.frontend_tokens, cfg.frontend_dim), jnp.bfloat16)
    else:  # decode: one new token against a seq_len cache
        specs["tokens"] = sds((B,), jnp.int32)
        specs["cache_index"] = sds((), jnp.int32)
        cache = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
        specs["cache"] = cache
    return specs
