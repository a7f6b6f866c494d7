"""Flash-decode: attention against the KV cache with a *vector-steered* grid.

The prefill flash kernel's block-skip logic is static (causal/window masks
known at trace time).  Decode's mask is the *cache length* — a runtime
quantity — so the valid-prefix bound rides the scalar-prefetch path instead.
PR 2 carried ONE scalar length (one token, one sequence); here the control
word is a **vector** of per-token lengths, the same promotion TileLoom makes
from whole-loop schedules to tile-granular plans:

* grid (B, T, nkv, Skv/bkv): T draft/speculative tokens attend in ONE launch
  instead of T, and one block carries every query head of one KV head (a
  (group, hd) tile: whole array dims, so the TPU tiling rule holds, and
  each KV tile is fetched once per group).  The KV BlockSpec index_maps clamp the block index per
  (b, t) against the prefetched length vector, so no DMA is ever issued for
  cache tail blocks beyond that token's prefix — the length vector literally
  steers which HBM blocks move, per token.
* per-token lengths double as the intra-launch causal mask between draft
  tokens: token t's length is ``base + t + 1``, so draft token t sees draft
  tokens < t and nothing after — speculative causality needs no extra mask
  plumbing.
* per-sequence lengths (ragged continuous batching) are the same vector with
  a batch-major stride — one launch serves sequences at different depths.

``pl.when(kv_base < length)`` skips the compute for re-mapped steps and an
in-block iota mask handles the ragged last block, exactly as in the scalar
kernel — per (b, t) the math (block order, online-softmax updates) is
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


def _load_kv_tile(kv_ref, scl_ref, row: int):
    """Load one (bkv, hd) KV tile as f32, dequantizing in place when the
    cache is int8: the per-token scales arrive as a (2, 1, bkv, 1) VMEM block
    that follows the KV tile (row 0 = K scales, row 1 = V scales) and are
    multiplied right after the tile load — BEFORE any dot — so the kernel is
    bitwise-equal to running the unquantized kernel on the jnp-dequantized
    buffer."""
    x = kv_ref[0, 0].astype(jnp.float32)  # (bkv, hd)
    if scl_ref is not None:
        x = x * scl_ref[row, 0]  # (bkv, 1) per-row scales
    return x


def _online_softmax_step(q_ref, k_ref, v_ref, scl_ref, m_ref, l_ref, acc_ref, valid, scale, zero_masked):
    """One KV block of the online softmax for one KV head's query group:
    q (group, hd) against the (bkv, hd) tile, ``valid`` a (1, bkv) mask."""
    q = q_ref[0, 0, 0].astype(jnp.float32)  # (group, hd)
    k = _load_kv_tile(k_ref, scl_ref, 0)  # (bkv, hd)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (group, bkv)
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
    v = _load_kv_tile(v_ref, scl_ref, 1)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(p, v, preferred_element_type=jnp.float32)


def _init_stats(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _store_out(o_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0, 0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _flash_decode_kernel(len_ref, anc_ref, base_ref, *refs, bkv: int, n_kv: int,
                         scale: float, T: int, quantized: bool, paged: bool):
    if paged:
        refs = refs[1:]  # the block table only steers the index_maps
    if quantized:
        q_ref, k_ref, v_ref, scl_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
        scl_ref = None
    b, t, ki = pl.program_id(0), pl.program_id(1), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        _init_stats(m_ref, l_ref, acc_ref)

    length = len_ref[b * T + t]  # this token's valid prefix (control word)
    anc = anc_ref[t]             # packed ancestor bitmask (-1 = chain: all set)
    base = base_ref[b]           # committed-prefix length (draft rows start here)
    kv_base = ki * bkv

    @pl.when(kv_base < length)
    def _compute():
        kv_pos = kv_base + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1)
        # rows below base are shared committed prefix; draft row base + u is
        # visible iff bit u of this node's ancestor word is set (arithmetic
        # shift: the chain word -1 keeps every bit, reducing the mask to the
        # pure length clamp — bitwise the linear kernel)
        u = kv_pos - base
        on_path = (u < 0) | (jnp.right_shift(anc, jnp.clip(u, 0, 31)) & 1 > 0)
        _online_softmax_step(
            q_ref, k_ref, v_ref, scl_ref, m_ref, l_ref, acc_ref,
            (kv_pos < length) & on_path, scale, zero_masked=False,
        )

    @pl.when(ki == n_kv - 1)
    def _store():
        _store_out(o_ref, l_ref, acc_ref)


def _stat_scratch(group: int, hd: int):
    return [
        pltpu.VMEM((group, 1), jnp.float32),
        pltpu.VMEM((group, 1), jnp.float32),
        pltpu.VMEM((group, hd), jnp.float32),
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
    k: jnp.ndarray,        # (B, nkv, Skv, hd) full cache buffer
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # (B*T,) int32 valid prefix length per token, >= 1
    anc_words: Optional[jnp.ndarray] = None,  # (T,) int32 ancestor bitmasks
    base: Optional[jnp.ndarray] = None,       # (B,) int32 committed-prefix length
    scales: Optional[jnp.ndarray] = None,     # (2, B, Skv) f32 per-row K/V scales
    *,
    bkv: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, T, nq, hd = q.shape
    nkv, Skv = k.shape[1], k.shape[2]
    group = nq // nkv
    scale = 1.0 / math.sqrt(hd)
    bkv = min(bkv, Skv)
    assert Skv % bkv == 0, "pad the cache to a block multiple in ops"
    n_kv = Skv // bkv
    quantized = scales is not None
    if anc_words is None:
        # chain default: all-ones words make the ancestor test vacuous and
        # the kernel bitwise-equal to the pure length-clamped linear kernel
        anc_words = jnp.full((T,), -1, jnp.int32)
    if base is None:
        base = jnp.zeros((B,), jnp.int32)

    def kv_block(b, t, h, ki, len_ref):
        # vector-steered: blocks past token (b, t)'s valid prefix re-map to
        # its last valid block (their compute is skipped), so their DMA never
        # happens — per-token clamping against the prefetched length vector.
        # The ancestor mask is applied inside the block; the length clamp
        # alone bounds which blocks move (tree rows are within it by the
        # topological-order invariant).
        return jnp.minimum(ki, (len_ref[b * T + t] - 1) // bkv)

    def kv_map(b, t, h, ki, len_ref, *_):
        return (b, h, kv_block(b, t, h, ki, len_ref), 0)

    def qo_map(b, t, h, ki, *_):
        return (b, t, h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, 1, group, hd), qo_map),
        pl.BlockSpec((1, 1, bkv, hd), kv_map),
        pl.BlockSpec((1, 1, bkv, hd), kv_map),
    ]
    operands = [_group_q(q, nkv), k, v]
    if quantized:
        # the scale rows follow the KV tile through the same clamped map
        in_specs.append(pl.BlockSpec(
            (2, 1, bkv, 1),
            lambda b, t, h, ki, len_ref, *_: (0, b, kv_block(b, t, h, ki, len_ref), 0),
        ))
        operands.append(scales.astype(jnp.float32).reshape(2, B, Skv, 1))
    kern = functools.partial(
        _flash_decode_kernel, bkv=bkv, n_kv=n_kv, scale=scale, T=T,
        quantized=quantized, paged=False,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
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
        name="flash_decode",
    )(lengths, anc_words.astype(jnp.int32), base.astype(jnp.int32), *operands)
    return out.reshape(B, T, nq, hd)


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
            q_ref, k_ref, v_ref, scl_ref, m_ref, l_ref, acc_ref, valid, scale,
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
    k: jnp.ndarray,        # (P, nkv, page_size, hd) physical page pool
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
    same map — while the in-kernel mask math (length clamp, ancestor words,
    online softmax) is identical to :func:`flash_decode_pallas` at
    ``bkv = page_size``.  With an identity table the chain default is
    therefore bitwise-equal to the contiguous kernel — the same contract the
    all-ones ancestor words uphold for trees vs chains.
    """
    B, T, nq, hd = q.shape
    P_, nkv, ps = k.shape[0], k.shape[1], k.shape[2]
    assert ps == page_size
    group = nq // nkv
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

    def kv_map(b, t, h, ki, len_ref, anc_ref, base_ref, tbl_ref):
        return (page(b, t, ki, len_ref, tbl_ref), h, 0, 0)

    def qo_map(b, t, h, ki, *_):
        return (b, t, h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, 1, group, hd), qo_map),
        pl.BlockSpec((1, 1, ps, hd), kv_map),
        pl.BlockSpec((1, 1, ps, hd), kv_map),
    ]
    operands = [_group_q(q, nkv), k, v]
    if quantized:
        # scales are page metadata in pool-row order: the (2, P, ps, 1) view
        # lets the scale rows ride the page's own block-table lookup
        in_specs.append(pl.BlockSpec(
            (2, 1, ps, 1),
            lambda b, t, h, ki, len_ref, anc_ref, base_ref, tbl_ref: (
                0, page(b, t, ki, len_ref, tbl_ref), 0, 0),
        ))
        operands.append(scales.astype(jnp.float32).reshape(2, P_, ps, 1))
    kern = functools.partial(
        _flash_decode_kernel, bkv=ps, n_kv=max_pages, scale=scale, T=T,
        quantized=quantized, paged=True,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, T, nkv, max_pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, 1, group, hd), qo_map),
            scratch_shapes=_stat_scratch(group, hd),
        ),
        out_shape=jax.ShapeDtypeStruct((B, T, nkv, group, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_decode_paged",
    )(
        lengths, anc_words.astype(jnp.int32), base.astype(jnp.int32),
        table.reshape(-1).astype(jnp.int32), *operands,
    )
    return out.reshape(B, T, nq, hd)


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
    k: jnp.ndarray,  # (B, Skv, nkv, hd) cache buffer (already holding this step's K)
    v: jnp.ndarray,
    cache_index: jnp.ndarray,  # scalar | (B,) | (B, T) int32 token position(s)
    *,
    ancestors: Optional[jnp.ndarray] = None,  # (T,) int32 packed ancestor words
    base: Optional[jnp.ndarray] = None,       # (B,) int32 committed-prefix length
    scales: Optional[jnp.ndarray] = None,     # (2, B, Skv) per-row K/V scales (int8 cache)
    bkv: int = 128,
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
    """
    run = functools.partial(_flash_decode, bkv=bkv, interpret=interpret)
    args = (q, k, v, jnp.asarray(cache_index, jnp.int32), ancestors, base, scales)
    return shard_over_heads(run, args, (2, 2, 2, None, None, None, None))


def _flash_decode(q, k, v, cache_index, ancestors, base, scales, *, bkv, interpret):
    it = resolve_interpret(interpret)
    B, T, nq, hd = q.shape
    Skv = k.shape[1]
    bkv_ = min(bkv, Skv)
    pad_kv = (-Skv) % bkv_
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if pad_kv:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_kv), (0, 0)))
    scl = None
    if scales is not None:
        scl = jnp.asarray(scales, jnp.float32)
        if pad_kv:  # pad with ones: padded rows are masked but still multiplied
            scl = jnp.pad(scl, ((0, 0), (0, 0), (0, pad_kv)), constant_values=1.0)
    lengths = _as_length_vector(cache_index, B, T)
    return flash_decode_pallas(
        q, kt, vt, lengths, anc_words=ancestors, base=base, scales=scl,
        bkv=bkv_, interpret=it,
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
    kt = jnp.swapaxes(k.reshape(P, page_size, *k.shape[1:]), 1, 2)
    vt = jnp.swapaxes(v.reshape(P, page_size, *v.shape[1:]), 1, 2)
    lengths = _as_length_vector(cache_index, B, T)
    return flash_decode_paged_pallas(
        q, kt, vt, lengths, pages.reshape(-1), anc_words=ancestors, base=base,
        scales=scales, page_size=page_size, interpret=it,
    )
