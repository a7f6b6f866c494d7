"""Flash-decode: attention against the KV cache with a *vector-steered* grid.

The prefill flash kernel's block-skip logic is static (causal/window masks
known at trace time).  Decode's mask is the *cache length* — a runtime
quantity — so the valid-prefix bound rides the scalar-prefetch path instead.
PR 2 carried ONE scalar length (one token, one sequence); here the control
word is a **vector** of per-token lengths, the same promotion TileLoom makes
from whole-loop schedules to tile-granular plans:

* grid (B, T, cdiv(S, bkv)) over the cache as the model stores it,
  (B, S, nkv, hd): T draft/speculative tokens attend in ONE launch instead
  of T, and one (1, bkv, nkv, hd) block holds bkv cache rows of every KV
  head.  Its trailing (nkv, hd) dims are whole array dims, so the TPU tiling
  rule holds for any head count and width, and the block is made of the
  stored layout's own tiles, so nothing is transposed or padded before the
  launch and each KV tile moves once per (b, t, block).  The KV BlockSpec
  index_maps clamp the block index per (b, t) against the prefetched length
  vector, so no DMA is ever issued for cache tail blocks beyond that
  token's prefix — the length vector literally steers which HBM blocks
  move, per token.
* inside a block the kernel reads the (bkv, nkv, hd) tile as bkv * nkv rows
  and scores every query head of the token against all of them; a
  head-match mask keeps, for each query head, the rows of its own KV head.
  That is nkv times the MXU and score work of a walk over the heads, and
  still cheaper: gathering one head's rows out of the stored tiles costs
  Mosaic a load per row, or a shuffle of every vreg of the block.  The
  reshape is free where nkv is a multiple of 8 (each cache row's (nkv, hd)
  is then whole (8, 128) tiles); elsewhere Mosaic moves sublanes to make it.
  Block rows come from the shape (``_block_rows``: one K block of about
  ``KV_BLOCK_BYTES`` of cache, its f32 working copy at most four times
  that, a narrow cache's rows padded to whole tiles).
* "as the model stores it" is the TPU's default layout for an array of the
  cache's shape.  That is row-major, which the kernel's operands must be,
  wherever the (8, 128) tiling of (nkv, hd) wastes little; at some widths
  (nkv 1 or 20, hd 96) XLA orders the cache otherwise, and then copies it
  to row-major before the launch.
* the decode scan keeps its layers' caches stacked, (L, B, S, nkv, hd), and
  writes each layer's new rows into the stack in place; with one more
  prefetched word, the layer index, the KV index_maps take their blocks
  straight out of the stack, so no layer is sliced out of it or put back.
* per-token lengths double as the intra-launch causal mask between draft
  tokens: token t's length is ``base + t + 1``, so draft token t sees draft
  tokens < t and nothing after — speculative causality needs no extra mask
  plumbing.
* per-sequence lengths (ragged continuous batching) are the same vector with
  a batch-major stride — one launch serves sequences at different depths.

``pl.when(kv_base < length)`` skips the compute for re-mapped steps.  S need
not be a multiple of bkv: rows at or past a token's length are masked out of
the scores by an in-block iota mask and zeroed in V, since the last block
may run past the cache's end, where the tile holds anything, and 0 * NaN is
NaN.  Per (b, t) the math (block order, online-softmax updates) is
IDENTICAL to a one-token launch, so a T-token launch is bitwise equal to T
sequential launches.

Tree drafts (branch-divergent control flow): the intra-draft causal mask is
no longer implicit in the ``base + t`` length structure — it is an explicit
**ancestor mask** riding the scalar-prefetch path alongside the lengths.
Each draft node ``t`` carries one packed int32 control word (bit ``u`` set
iff node ``u`` is on ``t``'s root path — the packed row of the launch's
``(T, T)`` ancestor table, compiled once per tree shape by
:class:`repro.core.plans.TreePlan`) plus the per-sequence base length.  A
cache row ``p`` is then valid for node ``t`` iff ``p < base`` (shared
committed prefix) or bit ``p - base`` of ``t``'s word is set — so ALL nodes
of a branchy draft attend in ONE launch while sharing the prefix KV blocks.
The linear draft is the degenerate chain whose ancestor words are all-ones:
the mask reduces to the pure length clamp bit-for-bit, which is what keeps
the chain path bitwise-identical to PR 3's vector-steered kernel.

Control-word invariants (what every caller must uphold):

* **Length-clamp contract** — ``lengths[b*T + t]`` bounds the highest cache
  row node ``(b, t)`` may touch (``base + t + 1``); the KV index_maps clamp
  the block walk against it BEFORE the ancestor mask is consulted, so no DMA
  is ever issued past a token's valid extent, tree or chain.
* **Topological rows** — draft node ``t`` must sit at cache row
  ``base + t`` with ``parents[t] < t``; the ancestor bit test
  ``(word >> (p - base)) & 1`` is only meaningful under that row layout.
* **Chain default** — when no tree is supplied the ancestor words are ``-1``
  (arithmetic shift keeps every bit set), making the mask a no-op and the
  kernel's output bitwise-equal to the pre-tree linear kernel.

The window-steered variant (:func:`flash_decode_window_pallas`) finishes the
rolling-cache story: local-attention caches are modulo-addressed (slot
``pos % W``), so the valid window is up to two contiguous slot segments
around the wrap point.  The kernel walks the W-sized buffer's blocks with the
index_map clamped to the written prefix — at most ``W`` KV bytes ever move,
regardless of the sequence position or ``max_len`` — and masks per (b, t) by
reconstructing each slot's absolute position from the prefetched position
vector.  Rolling layers thereby leave the masked-jnp path with the same
byte bound the rolling buffer already guarantees.  (Rolling buffers carry
``spec_tokens - 1`` slack slots so a draft's later writes never evict rows
still inside an earlier draft token's window; tree drafts are chain-only on
rolling layers.)
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret, shard_over_heads

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
# cache bytes of one K (or V) block of flash_decode: K and V, double-buffered,
# take four times this (more where a narrow cache's rows pad to whole tiles),
# and their f32 working copies up to eight times, inside the 16 MiB of
# scoped VMEM a v5e kernel gets (see _block_rows)
KV_BLOCK_BYTES = 512 * 1024


def _load_kv_tile(tile, scl_ref, row: int):
    """A (bkv, hd) or (bkv, nkv, hd) KV tile as f32, dequantized in place
    when the cache is int8: the per-row scales arrive as a (2, 1, bkv, 1)
    VMEM block that follows the KV block (row 0 = K scales, row 1 = V
    scales; one scale per cache row, shared by its heads) and are multiplied
    right after the tile load — BEFORE any dot — so the kernel is
    bitwise-equal to running the unquantized kernel on the jnp-dequantized
    buffer."""
    x = tile.astype(jnp.float32)
    if scl_ref is not None:
        s = scl_ref[row, 0]  # (bkv, 1) per-row scales
        x = x * (s if x.ndim == 2 else s[:, :, None])
    return x


def _online_softmax_step(q, k, v, m_ref, l_ref, acc_ref, valid, scale, zero_masked):
    """One KV block of the online softmax: q (rows, hd) against the f32
    (bkv, hd) tiles k and v, ``valid`` a (rows or 1, bkv) mask."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (rows, bkv)
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    if zero_masked:
        # a block may hold no valid slot for THIS query token (its window
        # sits in the other wrap segment): with m still NEG_INF, exp(s - m)
        # would be 1 on masked lanes — zero them explicitly
        p = jnp.where(valid, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
    m_ref[...] = m_new
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)


def _init_stats(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _store_out(o_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0, 0] = (acc_ref[...] / l).reshape(o_ref.shape[2:]).astype(o_ref.dtype)


def _flash_decode_kernel(len_ref, anc_ref, base_ref, *refs, bkv: int, n_kv: int,
                         scale: float, T: int, quantized: bool, steer_words: int):
    """One (b, t, block) step: every query head of token (b, t) against the
    block's ``bkv`` cache rows of every KV head."""
    # the block table or the layer word only steers the index_maps
    refs = refs[steer_words:]
    if quantized:
        q_ref, k_ref, v_ref, scl_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        scl_ref = None
    b, t, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        _init_stats(m_ref, l_ref, acc_ref)

    length = len_ref[b * T + t]  # this token's valid prefix (control word)
    anc = anc_ref[t]             # packed ancestor bitmask (-1 = chain: all set)
    base = base_ref[b]           # committed-prefix length (draft rows start here)
    kv_base = ki * bkv

    @pl.when(kv_base < length)
    def _compute():
        # the (bkv, nkv, hd) block read as bkv * nkv rows: row r is head
        # r % nkv of cache row kv_base + r // nkv (free where each cache
        # row's (nkv, hd) is whole tiles of the stored layout: nkv % 8 == 0)
        nkv, hd = k_ref.shape[2], k_ref.shape[3]
        k, v = (_load_kv_tile(ref[0], scl_ref, i).reshape(bkv * nkv, hd)
                for i, ref in enumerate((k_ref, v_ref)))
        q = q_ref[0, 0].astype(jnp.float32)  # (nq, hd)
        r = jax.lax.broadcasted_iota(jnp.int32, (1, bkv * nkv), 1)
        kv_pos = kv_base + r // nkv
        # rows below base are shared committed prefix; draft row base + u is
        # visible iff bit u of this node's ancestor word is set (arithmetic
        # shift: the chain word -1 keeps every bit, reducing the mask to the
        # pure length clamp — bitwise the linear kernel)
        u = kv_pos - base
        on_path = (u < 0) | (jnp.right_shift(anc, jnp.clip(u, 0, 31)) & 1 > 0)
        # every query head scores every row and keeps those of its own KV
        # head: nkv times the MXU work of a walk over the heads, but far less
        # than gathering each head's rows (Mosaic loads a row at a time, or
        # shuffles every vreg of the block)
        head = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0) // (q.shape[0] // nkv)
        valid = (kv_pos < length) & on_path & (r % nkv == head)
        # rows at or past the length are masked out of the scores, but may
        # lie past the cache's end (the ragged last block), where the tile
        # holds anything: zero them in V too, since 0 * NaN is NaN
        live = kv_base + jax.lax.broadcasted_iota(jnp.int32, (bkv * nkv, 1), 0) // nkv < length
        _online_softmax_step(
            q, k, jnp.where(live, v, 0.0), m_ref, l_ref, acc_ref, valid, scale,
            zero_masked=False,
        )

    @pl.when(ki == n_kv - 1)
    def _store():
        _store_out(o_ref, l_ref, acc_ref)


def _stat_scratch(rows: int, hd: int):
    return [
        pltpu.VMEM((rows, 1), jnp.float32),
        pltpu.VMEM((rows, 1), jnp.float32),
        pltpu.VMEM((rows, hd), jnp.float32),
    ]


def _group_q(q: jnp.ndarray, nkv: int) -> jnp.ndarray:
    """(B, T, nq, hd) -> (B, T, nkv, group, hd): one block holds every query
    head of one KV head, so the (group, hd) block dims are whole array dims
    and each KV tile is fetched once per group instead of once per head."""
    B, T, nq, hd = q.shape
    return q.reshape(B, T, nkv, nq // nkv, hd)


@functools.partial(jax.jit, static_argnames=("bkv", "interpret"))
def flash_decode_pallas(
    q: jnp.ndarray,        # (B, T, nq, hd) draft/step tokens
    k: jnp.ndarray,        # (B, Skv, nkv, hd) full cache buffer, as the model stores it;
                           # (L, B, Skv, nkv, hd) the layer-stacked cache with ``layer``
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # (B*T,) int32 valid prefix length per token, >= 1
    anc_words: Optional[jnp.ndarray] = None,  # (T,) int32 ancestor bitmasks
    base: Optional[jnp.ndarray] = None,       # (B,) int32 committed-prefix length
    scales: Optional[jnp.ndarray] = None,     # (2, B, Skv) f32 per-row K/V scales (of the layer)
    layer: Optional[jnp.ndarray] = None,      # int32 scalar: the layer of the stacked cache
    *,
    bkv: int,
    interpret: bool = False,
) -> jnp.ndarray:
    B, T, nq, hd = q.shape
    Skv, nkv = k.shape[-3], k.shape[-2]
    scale = 1.0 / math.sqrt(hd)
    n_kv = pl.cdiv(Skv, bkv)
    quantized = scales is not None
    stacked = layer is not None
    if anc_words is None:
        # chain default: all-ones words make the ancestor test vacuous and
        # the kernel bitwise-equal to the pure length-clamped linear kernel
        anc_words = jnp.full((T,), -1, jnp.int32)
    if base is None:
        base = jnp.zeros((B,), jnp.int32)

    def kv_block(b, t, ki, len_ref):
        # vector-steered: blocks past token (b, t)'s valid prefix re-map to
        # its last valid block (their compute is skipped), so their DMA never
        # happens — per-token clamping against the prefetched length vector.
        # The ancestor mask is applied inside the block; the length clamp
        # alone bounds which blocks move (tree rows are within it by the
        # topological-order invariant).
        return jnp.minimum(ki, (len_ref[b * T + t] - 1) // bkv)

    # the stacked cache's layer axis is squeezed out of the block: the
    # kernel sees the same (1, bkv, nkv, hd) block either way, taken at the
    # layer the prefetched layer word names
    lead = (None,) if stacked else ()

    def kv_map(b, t, ki, len_ref, anc_ref, base_ref, *layer_ref):
        return tuple(r[0] for r in layer_ref) + (b, kv_block(b, t, ki, len_ref), 0, 0)

    def qo_map(b, t, ki, *_):
        return (b, t, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, nq, hd), qo_map),
        pl.BlockSpec(lead + (1, bkv, nkv, hd), kv_map),
        pl.BlockSpec(lead + (1, bkv, nkv, hd), kv_map),
    ]
    operands = [q, k, v]
    if quantized:
        # the scale rows follow the KV block through the same clamped map
        in_specs.append(pl.BlockSpec(
            (2, 1, bkv, 1),
            lambda b, t, ki, len_ref, *_: (0, b, kv_block(b, t, ki, len_ref), 0),
        ))
        operands.append(scales.astype(jnp.float32).reshape(2, B, Skv, 1))
    words = [lengths, anc_words.astype(jnp.int32), base.astype(jnp.int32)]
    if stacked:
        words.append(layer.astype(jnp.int32).reshape(1))
    kern = functools.partial(
        _flash_decode_kernel, bkv=bkv, n_kv=n_kv, scale=scale, T=T,
        quantized=quantized, steer_words=int(stacked),
    )
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(words),
            grid=(B, T, n_kv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, nq, hd), qo_map),
            scratch_shapes=_stat_scratch(nq, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, nq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode",
    )(*words, *operands)


# ---------------------------------------------------------------------------
# window-steered variant for rolling (modulo-addressed) caches
# ---------------------------------------------------------------------------


def _flash_decode_window_kernel(pos_ref, *refs, bkv: int, n_kv: int, scale: float,
                                T: int, W: int, window: int, quantized: bool):
    if quantized:
        q_ref, k_ref, v_ref, scl_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        scl_ref = None
    b, t, ki = pl.program_id(0), pl.program_id(1), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        _init_stats(m_ref, l_ref, acc_ref)

    pos = pos_ref[b * T + t]          # this token's absolute position
    head = pos_ref[b * T + (T - 1)]   # last position written to this cache
    kv_base = ki * bkv

    # slots at/below the written prefix exist; blocks past it are re-mapped
    @pl.when(kv_base <= jnp.minimum(head, W - 1))
    def _compute():
        # reconstruct each slot's absolute position from the write head:
        # slot s holds the largest p <= head with p % W == s
        slot = kv_base + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        write = head % W
        abs_pos = head - jnp.remainder(write - slot, W)
        valid = (abs_pos >= 0) & (abs_pos <= pos) & (abs_pos > pos - window)
        _online_softmax_step(
            q_ref[0, 0, 0].astype(jnp.float32), _load_kv_tile(k_ref[0, 0], scl_ref, 0),
            _load_kv_tile(v_ref[0, 0], scl_ref, 1), m_ref, l_ref, acc_ref, valid, scale,
            zero_masked=True,
        )

    @pl.when(ki == n_kv - 1)
    def _store():
        _store_out(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("window", "bkv", "interpret"))
def flash_decode_window_pallas(
    q: jnp.ndarray,         # (B, T, nq, hd)
    k: jnp.ndarray,         # (B, nkv, W, hd) rolling cache buffer (slot = pos % W)
    v: jnp.ndarray,
    positions: jnp.ndarray, # (B*T,) int32 absolute position per token
    scales: Optional[jnp.ndarray] = None,  # (2, B, W) f32 per-slot K/V scales
    *,
    window: int,
    bkv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Window-steered decode over a rolling cache: at most two contiguous
    slot segments around the wrap point are valid; the index_map clamps the
    walk to the written prefix so at most W KV bytes move per (b, t, h)."""
    B, T, nq, hd = q.shape
    nkv, W = k.shape[1], k.shape[2]
    group = nq // nkv
    scale = 1.0 / math.sqrt(hd)
    bkv = min(bkv, W)
    assert W % bkv == 0, "choose bkv dividing the window buffer in ops"
    n_kv = W // bkv
    quantized = scales is not None

    def kv_block(b, ki, pos_ref):
        # clamp to the written prefix: before the first wrap only slots
        # [0, head] were ever written, so tail blocks re-map (compute skipped)
        head = pos_ref[b * T + (T - 1)]
        return jnp.minimum(ki, jnp.minimum(head, W - 1) // bkv)

    def qo_map(b, t, h, ki, pos_ref):
        return (b, t, h, 0, 0)

    def kv_map(b, t, h, ki, pos_ref):
        return (b, h, kv_block(b, ki, pos_ref), 0)

    in_specs = [
        pl.BlockSpec((1, 1, 1, group, hd), qo_map),
        pl.BlockSpec((1, 1, bkv, hd), kv_map),
        pl.BlockSpec((1, 1, bkv, hd), kv_map),
    ]
    operands = [_group_q(q, nkv), k, v]
    if quantized:
        in_specs.append(pl.BlockSpec(
            (2, 1, bkv, 1), lambda b, t, h, ki, pos_ref: (0, b, kv_block(b, ki, pos_ref), 0)
        ))
        operands.append(scales.astype(jnp.float32).reshape(2, B, W, 1))
    kern = functools.partial(
        _flash_decode_window_kernel, bkv=bkv, n_kv=n_kv, scale=scale, T=T, W=W,
        window=window, quantized=quantized,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, T, nkv, n_kv),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, 1, group, hd), qo_map),
            scratch_shapes=_stat_scratch(group, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, nkv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode_window",
    )(positions, *operands)
    return out.reshape(B, T, nq, hd)


# ---------------------------------------------------------------------------
# paged variant: block-table indirection on the scalar-prefetch path
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def flash_decode_paged_pallas(
    q: jnp.ndarray,        # (B, T, nq, hd)
    k: jnp.ndarray,        # (P, page_size, nkv, hd) physical page pool
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # (B*T,) int32 valid prefix length per token, >= 1
    table: jnp.ndarray,    # (B*max_pages,) int32 flattened block tables
    anc_words: Optional[jnp.ndarray] = None,  # (T,) int32 ancestor bitmasks
    base: Optional[jnp.ndarray] = None,       # (B,) int32 committed-prefix length
    scales: Optional[jnp.ndarray] = None,     # (2, R) f32 per-pool-row K/V scales
    *,
    page_size: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Paged flash-decode: one more prefetched control word — the block table.

    The grid walks LOGICAL pages (``max_pages`` per slot); the KV index_map
    composes the existing per-token length clamp with a block-table lookup
    (``page = table[b, ki]``), so each DMA pulls the physical page backing
    that logical block — the int8 scale rows of that page follow through the
    same map.  A page block is (1, page_size, nkv, hd), every KV head of the
    page's rows as the pool stores them, so the kernel — mask math (length
    clamp, ancestor words) and online softmax — is :func:`flash_decode_pallas`'s
    at ``bkv = page_size``.  With an identity table the chain default is
    therefore bitwise-equal to the contiguous kernel — the same contract the
    all-ones ancestor words uphold for trees vs chains.
    """
    B, T, nq, hd = q.shape
    P_, ps, nkv = k.shape[0], k.shape[1], k.shape[2]
    assert ps == page_size
    scale = 1.0 / math.sqrt(hd)
    max_pages = table.shape[0] // B
    quantized = scales is not None
    if anc_words is None:
        anc_words = jnp.full((T,), -1, jnp.int32)
    if base is None:
        base = jnp.zeros((B,), jnp.int32)

    def page(b, t, ki, len_ref, tbl_ref):
        # length clamp FIRST (logical blocks past the token's prefix re-map
        # to its last valid block; compute skipped), THEN the block-table
        # indirection to the physical page.  Unallocated entries (-1) can
        # only be reached beyond the clamp, so max() keeps the index legal.
        last = (len_ref[b * T + t] - 1) // ps
        return jnp.maximum(tbl_ref[b * max_pages + jnp.minimum(ki, last)], 0)

    def kv_map(b, t, ki, len_ref, anc_ref, base_ref, tbl_ref):
        return (page(b, t, ki, len_ref, tbl_ref), 0, 0, 0)

    def qo_map(b, t, ki, *_):
        return (b, t, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, nq, hd), qo_map),
        pl.BlockSpec((1, ps, nkv, hd), kv_map),
        pl.BlockSpec((1, ps, nkv, hd), kv_map),
    ]
    operands = [q, k, v]
    if quantized:
        # scales are page metadata in pool-row order: the (2, P, ps, 1) view
        # lets the scale rows ride the page's own block-table lookup
        in_specs.append(pl.BlockSpec(
            (2, 1, ps, 1),
            lambda b, t, ki, len_ref, anc_ref, base_ref, tbl_ref: (
                0, page(b, t, ki, len_ref, tbl_ref), 0, 0),
        ))
        operands.append(scales.astype(jnp.float32).reshape(2, P_, ps, 1))
    kern = functools.partial(
        _flash_decode_kernel, bkv=ps, n_kv=max_pages, scale=scale, T=T,
        quantized=quantized, steer_words=1,
    )
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, T, max_pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, nq, hd), qo_map),
            scratch_shapes=_stat_scratch(nq, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, nq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode_paged",
    )(
        lengths, anc_words.astype(jnp.int32), base.astype(jnp.int32),
        table.reshape(-1).astype(jnp.int32), *operands,
    )


# ---------------------------------------------------------------------------
# model-layout wrappers
# ---------------------------------------------------------------------------


def _as_length_vector(cache_index: jnp.ndarray, B: int, T: int) -> jnp.ndarray:
    """Promote a scalar / (B,) / (B, T) cache index to the (B*T,) length
    vector the kernel prefetches.

    scalar i       -> every token's prefix is [0, i + t]   (one sequence depth)
    (B,) idx       -> token (b, t) sees prefix [0, idx[b] + t]  (ragged batch)
    (B, T) idx     -> fully explicit per-token indices (draft trees)
    """
    idx = jnp.asarray(cache_index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    if idx.ndim == 1:
        idx = idx[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    return (idx + 1).reshape(B * T).astype(jnp.int32)


def flash_decode(
    q: jnp.ndarray,  # (B, T, nq, hd) — model layout (T = 1 for plain decode)
    k: jnp.ndarray,  # (B, Skv, nkv, hd) cache buffer (already holding this step's K),
                     # or the layer-stacked (L, B, Skv, nkv, hd) cache with ``layer``
    v: jnp.ndarray,
    cache_index: jnp.ndarray,  # scalar | (B,) | (B, T) int32 token position(s)
    *,
    ancestors: Optional[jnp.ndarray] = None,  # (T,) int32 packed ancestor words
    base: Optional[jnp.ndarray] = None,       # (B,) int32 committed-prefix length
    scales: Optional[jnp.ndarray] = None,     # (2, B, Skv) per-row K/V scales (int8 cache);
                                              # (2, L, B, Skv) with ``layer``
    layer: Optional[jnp.ndarray] = None,      # int32 scalar: the layer of a stacked cache
    bkv: Optional[int] = None,                # cache rows per block; None: from the shape
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Multi-token attention over each token's valid cache prefix.

    Token (b, t) attends to cache positions [0, index(b, t)] where the index
    vector is derived from ``cache_index`` (see :func:`_as_length_vector`) —
    one launch covers a whole speculative draft and/or a ragged batch.

    With ``ancestors``/``base`` the draft rows are additionally masked by the
    tree's ancestor table: node (b, t) sees committed rows ``[0, base[b])``
    plus exactly the draft rows ``base[b] + u`` whose bit ``u`` is set in
    ``ancestors[t]`` (see :class:`repro.core.plans.TreePlan.ancestor_words`).
    Without them every draft row at or below the token's own row is visible —
    the linear-chain behaviour, bit-for-bit.

    With ``layer`` the cache is the scan's layer-stacked buffer and the
    kernel reads layer ``layer``'s blocks straight out of it (one more
    prefetched word steering the index maps): bitwise the call on
    ``k[layer]``, without slicing the layer out.  Only the layer's scale
    rows, (2, B, Skv), are taken out of stacked ``scales``: the kernel's
    ``(2, B, Skv, 1)`` view of them pads each row to a lane tile, so a
    view of the whole stack would be rewritten every layer.
    """
    if (layer is None) != (k.ndim == 4):
        raise ValueError(
            "flash_decode takes a (B, S, nkv, hd) cache, or a layer-stacked "
            f"(L, B, S, nkv, hd) cache with layer=; got {k.ndim}-D, layer={layer}"
        )
    heads = k.ndim - 2
    run = functools.partial(_flash_decode, bkv=bkv, interpret=interpret)
    args = (q, k, v, jnp.asarray(cache_index, jnp.int32), ancestors, base, scales,
            None if layer is None else jnp.asarray(layer, jnp.int32))
    return shard_over_heads(run, args, (2, heads, heads, None, None, None, None, None))


def _block_rows(S: int, nkv: int, hd: int, dtype) -> int:
    """Cache rows per KV block: the most that keep one K block within
    ``KV_BLOCK_BYTES`` of cache and its f32 working copy in VMEM, each row's
    (nkv, hd) padded to whole (8, 128) tiles, within four times that (a
    narrow cache, nkv < 8, pads up to 8x).  A multiple of 8 rows, so the
    int8 scale block tiles; never more than the cache holds."""
    f32_row = -(-nkv // 8) * 8 * (-(-hd // 128) * 128) * 4
    rows = min(KV_BLOCK_BYTES // (nkv * hd * jnp.dtype(dtype).itemsize),
               4 * KV_BLOCK_BYTES // f32_row)
    return min(max(8, rows - rows % 8), S)


def _flash_decode(q, k, v, cache_index, ancestors, base, scales, layer, *, bkv, interpret):
    it = resolve_interpret(interpret)
    B, T, nq, hd = q.shape
    S, nkv = k.shape[-3], k.shape[-2]
    bkv = _block_rows(S, nkv, hd, k.dtype) if bkv is None else min(bkv, S)
    with jax.named_scope("attn.kv_read"):  # the control words; the cache is read in place
        lengths = _as_length_vector(cache_index, B, T)
        if layer is not None and scales is not None:
            scales = jax.lax.dynamic_index_in_dim(scales, layer, axis=1, keepdims=False)
    return flash_decode_pallas(
        q, k, v, lengths, anc_words=ancestors, base=base, scales=scales,
        layer=layer, bkv=bkv, interpret=it,
    )


def flash_decode_window(
    q: jnp.ndarray,  # (B, T, nq, hd) — model layout
    k: jnp.ndarray,  # (B, W, nkv, hd) rolling cache buffer (slot = pos % W)
    v: jnp.ndarray,
    cache_index: jnp.ndarray,  # scalar | (B,) int32 position of token (b, 0)
    *,
    window: int,
    scales: Optional[jnp.ndarray] = None,  # (2, B, W) per-slot K/V scales (int8 cache)
    bkv: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Window-steered attention over a rolling cache: token (b, t) at
    absolute position ``index(b) + t`` sees positions in
    ``(pos - window, pos]`` through the wrap point."""
    run = functools.partial(_flash_decode_window, window=window, bkv=bkv, interpret=interpret)
    args = (q, k, v, jnp.asarray(cache_index, jnp.int32), scales)
    return shard_over_heads(run, args, (2, 2, 2, None, None))


def _flash_decode_window(q, k, v, cache_index, scales, *, window, bkv, interpret):
    it = resolve_interpret(interpret)
    B, T, nq, hd = q.shape
    W = k.shape[1]
    # bkv must divide W so block -> slot arithmetic survives the wrap
    bkv_ = min(bkv, W)
    while W % bkv_:
        bkv_ //= 2
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    idx = jnp.asarray(cache_index, jnp.int32)
    if idx.ndim == 0:
        idx = jnp.broadcast_to(idx, (B,))
    positions = (idx[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]).reshape(B * T)
    return flash_decode_window_pallas(
        q, kt, vt, positions, scales=scales, window=window, bkv=bkv_, interpret=it,
    )


def flash_decode_paged(
    q: jnp.ndarray,  # (B, T, nq, hd) — model layout
    k: jnp.ndarray,  # (R, nkv, hd) flat physical page pool, R = P * page_size
    v: jnp.ndarray,
    cache_index: jnp.ndarray,  # scalar | (B,) | (B, T) int32 token position(s)
    pages: jnp.ndarray,        # (B, max_pages) int32 block tables (-1 = unallocated)
    *,
    page_size: int,
    ancestors: Optional[jnp.ndarray] = None,  # (T,) int32 packed ancestor words
    base: Optional[jnp.ndarray] = None,       # (B,) int32 committed-prefix length
    scales: Optional[jnp.ndarray] = None,     # (2, R) per-pool-row K/V scales (int8 pool)
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Paged multi-token attention: :func:`flash_decode` semantics against a
    flat page pool addressed through per-slot block tables.

    The pool row backing logical position ``pos`` of slot ``b`` is
    ``pages[b, pos // page_size] * page_size + pos % page_size``; the lookup
    rides the scalar-prefetch path as one more control word.  With the
    identity table the chain default is bitwise-equal to
    :func:`flash_decode` at ``bkv = page_size``.
    """
    run = functools.partial(_flash_decode_paged, page_size=page_size, interpret=interpret)
    args = (q, k, v, jnp.asarray(cache_index, jnp.int32), pages, ancestors, base, scales)
    return shard_over_heads(run, args, (2, 1, 1, None, None, None, None, None))


def _flash_decode_paged(q, k, v, cache_index, pages, ancestors, base, scales, *,
                        page_size, interpret):
    it = resolve_interpret(interpret)
    B, T, nq, hd = q.shape
    R = k.shape[0]
    assert R % page_size == 0, "pool rows must be a whole number of pages"
    P = R // page_size
    with jax.named_scope("attn.kv_read"):  # the control words; the pool is read in place
        lengths = _as_length_vector(cache_index, B, T)
    return flash_decode_paged_pallas(
        q, k.reshape(P, page_size, *k.shape[1:]), v.reshape(P, page_size, *v.shape[1:]), lengths, pages.reshape(-1), anc_words=ancestors, base=base,
        scales=scales, page_size=page_size, interpret=it,
    )
