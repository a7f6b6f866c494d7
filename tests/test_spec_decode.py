"""Vector-steered decode: speculative multi-token launches, rolling-window
flash-decode, and the continuous-batching serve semantics.

The contract under test, layer by layer:

* kernel — ONE launch over T draft tokens (per-token lengths on the
  scalar-prefetch path) is BITWISE equal to T sequential single-token
  launches; the window-steered variant matches the masked rolling-jnp path
  across the wrap point.
* model — ``decode_tokens`` reproduces T sequential ``decode_step`` calls
  exactly (plan carry included), and the plan-vector cache makes the
  reproduction survive draft rejection (rollback re-joins the sequential
  trace).
* serve — the greedy verify/rollback loop emits the SAME token sequence as
  plain sequential greedy decode, for any drafter.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import layers as L
from repro.models import transformer as T
from repro.models.model import Model

jax.config.update("jax_platform_name", "cpu")


def _moe_cfg(**kw):
    return dataclasses.replace(get_smoke_config("qwen3-moe-235b-a22b"), **kw)


# ---------------------------------------------------------------------------
# kernels: vector-steered multi-token launches (interpret mode)
# ---------------------------------------------------------------------------


def test_flash_decode_multi_token_bitwise_vs_sequential():
    """One (B, T, nq, Skv/bkv) launch == T single-token launches, bitwise:
    per token the block walk and online-softmax updates are identical."""
    from repro.kernels.flash_attention import flash_decode

    rng = np.random.default_rng(0)
    B, Tn, nq, nkv, hd, S, base = 2, 4, 8, 2, 32, 48, 9
    q = jnp.asarray(rng.standard_normal((B, Tn, nq, hd)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((B, S, nkv, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((B, S, nkv, hd)), jnp.float32)
    multi = flash_decode(q, ck, cv, jnp.int32(base), bkv=16, interpret=True)
    for t in range(Tn):
        single = flash_decode(q[:, t : t + 1], ck, cv, jnp.int32(base + t), bkv=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(multi[:, t : t + 1]), np.asarray(single))


def test_flash_decode_ragged_lengths_bitwise():
    """A (B,) length vector serves sequences at different depths in one
    launch — each (b, t) cell equals its own single-sequence launch."""
    from repro.kernels.flash_attention import flash_decode

    rng = np.random.default_rng(1)
    B, Tn, nq, nkv, hd, S = 3, 2, 4, 2, 16, 32
    q = jnp.asarray(rng.standard_normal((B, Tn, nq, hd)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((B, S, nkv, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((B, S, nkv, hd)), jnp.float32)
    idx = jnp.asarray([0, 13, 29], jnp.int32)
    got = flash_decode(q, ck, cv, idx, bkv=16, interpret=True)
    for b in range(B):
        for t in range(Tn):
            single = flash_decode(
                q[b : b + 1, t : t + 1], ck[b : b + 1], cv[b : b + 1],
                jnp.int32(int(idx[b]) + t), bkv=16, interpret=True,
            )
            np.testing.assert_array_equal(np.asarray(got[b : b + 1, t : t + 1]), np.asarray(single))


# positions cover: before the buffer fills, the fill boundary, straddling the
# wrap, and deep post-wrap steady state
@pytest.mark.parametrize("base", [0, 5, 13, 17, 40])
def test_flash_decode_window_matches_rolling_reference(base):
    """Window-steered kernel == masked rolling-jnp attention, including the
    intra-draft causal mask, across the wrap point of a modulo cache."""
    from repro.kernels.flash_attention import flash_decode_window

    rng = np.random.default_rng(base)
    B, Tn, nq, nkv, hd, W, window = 2, 3, 4, 2, 16, 16, 16
    q = jnp.asarray(rng.standard_normal((B, Tn, nq, hd)), jnp.float32)
    ck = jnp.asarray(rng.standard_normal((B, W, nkv, hd)), jnp.float32)
    cv = jnp.asarray(rng.standard_normal((B, W, nkv, hd)), jnp.float32)
    got = flash_decode_window(q, ck, cv, jnp.int32(base), window=window, bkv=8, interpret=True)

    head = base + Tn - 1
    slot = jnp.arange(W)
    abs_pos = head - jnp.remainder((head % W) - slot, W)  # (W,)
    for t in range(Tn):
        pos = base + t
        valid = (abs_pos >= 0) & (abs_pos <= pos) & (abs_pos > pos - window)
        qg = q[:, t : t + 1].reshape(B, 1, nkv, nq // nkv, hd)
        s = jnp.einsum("bsngh,btnh->bngst", qg, ck) / np.sqrt(hd)
        s = jnp.where(valid[None, None, None, None, :], s, -0.7 * np.finfo(np.float32).max)
        w = jax.nn.softmax(s, axis=-1)
        want = jnp.einsum("bngst,btnh->bsngh", w, cv).reshape(B, 1, nq, hd)
        np.testing.assert_allclose(
            np.asarray(got[:, t : t + 1]), np.asarray(want), rtol=1e-5, atol=1e-5
        )


# ---------------------------------------------------------------------------
# rolling-cache wrap semantics: prefill writes at pos % W, decode reads
# across the wrap — must equal the unwrapped full-history reference
# ---------------------------------------------------------------------------


def _unwrapped_local_logits(model, params, seq, window):
    """Oracle: full-sequence forward (blockwise attention over the UNWRAPPED
    history with a window mask — no rolling cache involved), last position."""
    cache = model.init_cache(seq.shape[0], seq.shape[1])
    logits, _ = jax.jit(model.prefill)(params, seq, cache)
    return logits


@pytest.mark.parametrize("use_kernel", [False, True])
def test_rolling_cache_decode_matches_unwrapped_reference(use_kernel):
    """Greedy decode through the W-sized rolling cache, across the wrap
    point, must match re-running the full unwrapped sequence each step —
    on the masked-jnp path and on the window-steered kernel path.

    Uses a dense (non-MoE) config so the oracle is exact: the decode plane's
    MoE plan is one step stale by design, which would show up here as a
    routing difference rather than an attention bug."""
    W = 8
    cfg = dataclasses.replace(
        get_smoke_config("qwen3-32b"),
        num_layers=1, attention_kind="local", local_window=W,
        decode_plane=True, use_pallas=use_kernel,
    )
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S, G = 2, 6, 6  # decode positions 6..11 cross the wrap at 8
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    max_len = S + G + 1

    cache = model.init_cache(B, max_len)
    logits, cache = jax.jit(model.prefill)(params, prompts, cache)
    seq = prompts
    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    dec = jax.jit(model.decode_step)
    for i in range(G):
        seq = jnp.concatenate([seq, toks[:, None]], axis=1)
        ref = _unwrapped_local_logits(model, params, seq, W)
        logits, cache = dec(params, cache, toks, jnp.int32(S + i))
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(ref), rtol=2e-4, atol=2e-4,
            err_msg=f"step {i} (pos {S + i}, wrap at {W})",
        )
        toks = jnp.argmax(logits, -1).astype(jnp.int32)


def test_rolling_spec_layer_kernel_matches_jnp_path():
    """The multi-token rolling layer gives identical attention on the
    window-kernel path (use_pallas, interpret) and the masked-jnp path."""
    W = 8
    B, Tn = 2, 3
    cfgs = {
        up: _moe_cfg(attention_kind="local", local_window=W, decode_plane=True,
                     spec_tokens=Tn, use_pallas=up)
        for up in (False, True)
    }
    p = T.init_layer(jax.random.PRNGKey(0), "attn", cfgs[False], jnp.float32)
    rng = np.random.default_rng(2)
    xn = jnp.asarray(rng.standard_normal((B, Tn, cfgs[False].d_model)), jnp.float32)
    cache = {
        "k": jnp.asarray(rng.standard_normal((B, W, cfgs[False].num_kv_heads, cfgs[False].resolved_head_dim)), jnp.float32),
        "v": jnp.asarray(rng.standard_normal((B, W, cfgs[False].num_kv_heads, cfgs[False].resolved_head_dim)), jnp.float32),
    }
    lengths = jnp.asarray([5, 11], jnp.int32)  # one pre-wrap, one post-wrap
    outs = {}
    for up, cfg in cfgs.items():
        outs[up], _ = T._decode_attn_rolling_spec(xn, p["attn"], cfg, dict(cache), lengths, W)
    np.testing.assert_allclose(
        np.asarray(outs[True]), np.asarray(outs[False]), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# model: speculative launches reproduce sequential decode, with rollback
# ---------------------------------------------------------------------------


def _sequential_trace(cfg, params, prompts, max_len, gen):
    model = Model(dataclasses.replace(cfg, spec_tokens=1))
    cache = model.init_cache(prompts.shape[0], max_len)
    logits, cache = jax.jit(model.prefill)(params, prompts, cache)
    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    dec = jax.jit(model.decode_step)
    S = prompts.shape[1]
    all_logits, all_toks = [], [toks]
    for i in range(gen):
        logits, cache = dec(params, cache, toks, jnp.int32(S + i))
        toks = jnp.argmax(logits, -1).astype(jnp.int32)
        all_logits.append(np.asarray(logits))
        all_toks.append(toks)
    return all_logits, all_toks


def test_decode_tokens_matches_sequential_steps_full_accept():
    """T=4 oracle drafts through decode_tokens == 4 sequential decode_steps,
    across two launches (exercising the plan-vector carry)."""
    Tn = 4
    cfg = _moe_cfg(decode_plane=True)
    B, S = 2, 8
    max_len = S + 2 * Tn + 1
    mspec = Model(dataclasses.replace(cfg, spec_tokens=Tn))
    params = mspec.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    seq_logits, seq_toks = _sequential_trace(cfg, params, prompts, max_len, 2 * Tn)

    cache = mspec.init_cache(B, max_len)
    _, cache = jax.jit(mspec.prefill)(params, prompts, cache)
    dtok = jax.jit(mspec.decode_tokens)
    for launch in range(2):
        draft = jnp.stack(seq_toks[launch * Tn : (launch + 1) * Tn], axis=1)
        lens = jnp.full((B,), S + launch * Tn, jnp.int32)
        acc = jnp.full((B,), 0 if launch == 0 else Tn - 1, jnp.int32)
        lg, cache = dtok(params, cache, draft, lens, acc)
        for t in range(Tn):
            np.testing.assert_allclose(
                np.asarray(lg[:, t]), seq_logits[launch * Tn + t],
                rtol=1e-5, atol=1e-5, err_msg=f"launch {launch} t {t}",
            )


def test_decode_tokens_rollback_rejoins_sequential_trace():
    """A rejected draft position must not contaminate later launches: the
    plan row selected by prev_accept and the overwritten KV rows make the
    relaunch bitwise-faithful to the sequential trace."""
    Tn = 4
    cfg = _moe_cfg(decode_plane=True)
    B, S = 2, 8
    max_len = S + 2 * Tn + 2
    mspec = Model(dataclasses.replace(cfg, spec_tokens=Tn))
    params = mspec.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    seq_logits, seq_toks = _sequential_trace(cfg, params, prompts, max_len, 2 * Tn)

    cache = mspec.init_cache(B, max_len)
    _, cache = jax.jit(mspec.prefill)(params, prompts, cache)
    dtok = jax.jit(mspec.decode_tokens)
    # draft wrong at position 2 -> greedy verification accepts 2 new tokens
    bad = jnp.stack(
        [seq_toks[0], seq_toks[1], (seq_toks[2] + 1) % cfg.vocab_size, seq_toks[3]], axis=1
    )
    lgb, cache = dtok(params, cache, bad, jnp.full((B,), S, jnp.int32), jnp.zeros((B,), jnp.int32))
    np.testing.assert_allclose(np.asarray(lgb[:, 0]), seq_logits[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lgb[:, 1]), seq_logits[1], rtol=1e-5, atol=1e-5)
    # relaunch from the accepted prefix: lengths += 2, plan row 1 consumed
    nxt = jnp.stack(seq_toks[2 : 2 + Tn], axis=1)
    lgn, cache = dtok(params, cache, nxt, jnp.full((B,), S + 2, jnp.int32), jnp.full((B,), 1, jnp.int32))
    for t in range(Tn):
        np.testing.assert_allclose(
            np.asarray(lgn[:, t]), seq_logits[2 + t], rtol=1e-5, atol=1e-5, err_msg=f"t {t}"
        )


def test_rolling_window_speculative_matches_sequential():
    """Speculative launches through a rolling-window layer must reproduce
    sequential rolling decode: the buffer carries spec_tokens - 1 slack
    slots, so writing all T drafts before attending never evicts positions
    still inside an earlier draft token's window (regression: with exactly
    ``window`` slots, draft 0 lost its window tail and logits diverged)."""
    W, Tn = 8, 3
    cfg = dataclasses.replace(
        get_smoke_config("qwen3-32b"), num_layers=1,
        attention_kind="local", local_window=W, decode_plane=True,
    )
    B, S, gen = 2, 6, 6
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    max_len = S + gen + Tn + 1
    mspec = Model(dataclasses.replace(cfg, spec_tokens=Tn))
    params = mspec.init(jax.random.PRNGKey(0))
    seq_logits, seq_toks = _sequential_trace(cfg, params, prompts, max_len, gen)

    cache = mspec.init_cache(B, max_len)
    _, cache = jax.jit(mspec.prefill)(params, prompts, cache)
    dtok = jax.jit(mspec.decode_tokens)
    for launch in range(2):  # second launch crosses the wrap at W=8
        draft = jnp.stack(seq_toks[launch * Tn : (launch + 1) * Tn], axis=1)
        lens = jnp.full((B,), S + launch * Tn, jnp.int32)
        acc = jnp.full((B,), 0 if launch == 0 else Tn - 1, jnp.int32)
        lg, cache = dtok(params, cache, draft, lens, acc)
        for t in range(Tn):
            np.testing.assert_allclose(
                np.asarray(lg[:, t]), seq_logits[launch * Tn + t],
                rtol=1e-5, atol=1e-5, err_msg=f"launch {launch} t {t}",
            )


def test_decode_tokens_supports_recurrent_layers_at_width_one():
    """The continuous-batching loop serves rec/ssm archs at spec width 1:
    decode_tokens(T=1) must match decode_step for a hybrid recurrent arch."""
    cfg = dataclasses.replace(get_smoke_config("recurrentgemma-2b"), decode_plane=True)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S, gen = 2, 6, 3
    max_len = S + gen + 1
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)

    cache_a = model.init_cache(B, max_len)
    logits, cache_a = jax.jit(model.prefill)(params, prompts, cache_a)
    cache_b = jax.tree.map(lambda x: x, cache_a)
    toks = jnp.argmax(logits, -1).astype(jnp.int32)
    dec = jax.jit(model.decode_step)
    dtok = jax.jit(model.decode_tokens)
    for i in range(gen):
        la, cache_a = dec(params, cache_a, toks, jnp.int32(S + i))
        lb, cache_b = dtok(params, cache_b, toks[:, None], jnp.full((B,), S + i, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb[:, 0]), rtol=1e-5, atol=1e-5, err_msg=f"step {i}"
        )
        toks = jnp.argmax(la, -1).astype(jnp.int32)


# the decode scan keeps the stacked cache in its carry; each case against the
# scan that sliced every layer's cache out of the stack and restacked it:
# (preset, overrides, kernels, draft tree)
STACKED = {
    "attn_local_jnp": ("qwen3-32b", dict(block_pattern=("attn", "local"), num_layers=4,
                                         local_window=8), False, None),
    "attn_local_kernel": ("qwen3-32b", dict(block_pattern=("attn", "local"), num_layers=4,
                                            local_window=8, decode_plane=True), True, None),
    "int8_kernel": ("qwen3-32b", dict(kv_dtype="int8"), True, None),
    "tree_kernel": ("qwen3-32b", dict(spec_tokens=4), True, (2, 1)),
    "paged_kernel": ("qwen3-32b", dict(block_pattern=("attn", "local"), num_layers=4,
                                       local_window=8, paged=True, page_size=8), True, None),
    "moe_plane_jnp": ("qwen3-moe-235b-a22b", dict(decode_plane=True, spec_tokens=2), False, None),
    "recurrent_jnp": ("recurrentgemma-2b", dict(num_layers=6, decode_plane=True), False, None),
}


def _random_cache(cfg, cache, key):
    """Every leaf drawn at random: KV rows, positive scales, valid plan ids."""
    leaves, tdef = jax.tree_util.tree_flatten_with_path(cache)
    out = []
    for (path, leaf), k in zip(leaves, jax.random.split(key, len(leaves))):
        name = path[-1].key
        if name == "plan_e":
            out.append(jax.random.randint(k, leaf.shape, 0, cfg.num_experts, leaf.dtype))
        elif name in ("ks", "vs", "pks", "pvs", "plan_w"):
            out.append(jax.random.uniform(k, leaf.shape, leaf.dtype, 0.01, 0.1))
        elif leaf.dtype == jnp.int8:
            out.append(jax.random.randint(k, leaf.shape, -127, 128, jnp.int32).astype(jnp.int8))
        else:
            out.append(jax.random.normal(k, leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(tdef, out)


def _sliced_decode_tokens(model, params, cache, tokens, lengths, prev_accept, **kw):
    """``decode_tokens`` with the cache in the scan's xs and ys: each layer's
    cache sliced out of the stack and restacked (no ``rest`` layers)."""
    cfg = model.cfg
    pat = cfg.block_pattern
    x = L.embed(tokens, params["embed"], jnp.dtype(cfg.dtype))
    x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)

    def sb_fn(carry, xs):
        h, rs = carry
        p_sb, c_sb = xs
        new_c = {}
        for j, kind in enumerate(pat):
            h, rs, new_c[f"b{j}"], _ = T.apply_layer_decode_spec(
                h, rs, p_sb[f"b{j}"], c_sb[f"b{j}"], kind, cfg, lengths, prev_accept,
                model.moe_apply, decode_apply=model.decode_moe_apply, **kw,
            )
        return (h, rs), new_c

    (x, _), scan = jax.lax.scan(sb_fn, (x, x), (params["blocks"]["scan"], cache["scan"]))
    return model.logits(params, x), {"scan": scan, "rest": cache["rest"]}


@pytest.mark.parametrize("case", list(STACKED))
def test_decode_tokens_stacked_cache_matches_sliced_scan(case):
    """Logits and the whole new cache are bitwise those of the scan that
    sliced each layer's cache out and restacked it: contiguous attention
    in place, rolling, paged, plan and recurrent leaves taken out and put
    back."""
    from repro.core.plans import TreePlan

    preset, over, kernels, branching = STACKED[case]
    cfg = dataclasses.replace(get_smoke_config(preset), use_pallas=kernels, **over)
    assert cfg.num_layers % len(cfg.block_pattern) == 0
    model = Model(cfg)
    B, max_len, Tn = 2, 24, max(cfg.spec_tokens, 1)
    params = model.init(jax.random.PRNGKey(0))
    cache = _random_cache(cfg, model.init_cache(B, max_len), jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, Tn), 0, cfg.vocab_size)
    lengths = jnp.asarray([3, max_len - Tn - 1], jnp.int32)
    prev = jnp.asarray([0, Tn - 1], jnp.int32)
    kw = {}
    if branching:
        kw["tree"] = TreePlan.from_branching(list(branching)).validate()
    if cfg.paged:
        kw["pages"] = T.identity_page_table(cfg, B, max_len)
    got_l, got_c = jax.jit(lambda c: model.decode_tokens(params, c, tokens, lengths, prev, **kw))(cache)
    want_l, want_c = jax.jit(
        lambda c: _sliced_decode_tokens(model, params, c, tokens, lengths, prev, **kw))(cache)
    np.testing.assert_array_equal(np.asarray(got_l), np.asarray(want_l))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                 got_c, want_c)


def test_serve_verify_rollback_equals_sequential_greedy():
    """The continuous-batching verify/rollback loop produces the SAME token
    sequence as sequential greedy decode, whatever the drafter proposes —
    here the worst case (repeat-last-token drafts)."""
    Tn = 3
    gen = 7
    cfg = _moe_cfg(decode_plane=True)
    B, S = 2, 8
    max_len = S + gen + Tn + 1
    mspec = Model(dataclasses.replace(cfg, spec_tokens=Tn))
    params = mspec.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
    _, seq_toks = _sequential_trace(cfg, params, prompts, max_len, gen)
    want = np.stack([np.asarray(t) for t in seq_toks], axis=1)  # (B, gen+1)

    cache = mspec.init_cache(B, max_len)
    logits, cache = jax.jit(mspec.prefill)(params, prompts, cache)
    last = jnp.argmax(logits, -1).astype(jnp.int32)
    dtok = jax.jit(mspec.decode_tokens)
    lengths = np.full((B,), S, np.int32)
    prev_accept = np.zeros((B,), np.int32)
    history = [[int(v)] for v in np.asarray(last)]
    gen_left = np.full((B,), gen, np.int32)
    while (gen_left > 0).any():
        toks = np.tile(np.asarray(last)[:, None], (1, Tn))  # repeat drafter
        lg, cache = dtok(
            params, cache, jnp.asarray(toks), jnp.asarray(lengths), jnp.asarray(prev_accept)
        )
        y = np.asarray(jnp.argmax(lg, -1))
        nxt = np.asarray(last).copy()
        for b in range(B):
            if gen_left[b] <= 0:
                continue
            a = 1
            while a < Tn and a < gen_left[b] and toks[b, a] == y[b, a - 1]:
                a += 1
            history[b].extend(int(v) for v in y[b, :a])
            lengths[b] += a
            gen_left[b] -= a
            prev_accept[b] = a - 1
            nxt[b] = y[b, a - 1]
        last = jnp.asarray(nxt)
    got = np.stack([np.asarray(h[: gen + 1]) for h in history], axis=0)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# plan telemetry + continuous-batching admission
# ---------------------------------------------------------------------------


def test_plan_telemetry_perfect_agreement_for_zero_router():
    """With a zero router every plan is the uniform top-k — stale and fresh
    always agree, so the telemetry metric must be exactly 1."""
    Tn = 3
    cfg = _moe_cfg(decode_plane=True, spec_tokens=Tn)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map_with_path(
        lambda path, l: jnp.zeros_like(l)
        if any(getattr(k, "key", "") == "router" for k in path)
        else l,
        params,
    )
    B, S = 2, 6
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    cache = model.init_cache(B, S + Tn + 1)
    _, cache = jax.jit(model.prefill)(params, prompts, cache)
    toks = jnp.zeros((B, Tn), jnp.int32)
    _, _, metrics = jax.jit(
        lambda p, c, t, l, a: model.decode_tokens(p, c, t, l, a, telemetry=True)
    )(params, cache, toks, jnp.full((B,), S, jnp.int32), jnp.zeros((B,), jnp.int32))
    assert float(metrics["plan_agreement"]) == pytest.approx(1.0)


def test_topk_agreement_metric():
    from repro.core.control_plane import topk_agreement

    a = jnp.asarray([[0, 1], [2, 3], [4, 5]], jnp.int32)
    b = jnp.asarray([[1, 0], [2, 7], [6, 5]], jnp.int32)
    # rows: identical sets (1.0), one common (1/3), one common (1/3)
    want = (1.0 + 1 / 3 + 1 / 3) / 3
    assert float(topk_agreement(a, b)) == pytest.approx(want)


def test_cache_slot_admission_matches_independent_decode():
    """B=1 prefill written into a slot of a ragged batch must decode exactly
    like an independent single-sequence run (continuous-batching admission)."""
    Tn = 2
    cfg = _moe_cfg(decode_plane=True, spec_tokens=Tn)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    max_len, B = 20, 3
    prefill = jax.jit(model.prefill)
    admit = jax.jit(model.write_cache_slot)
    dtok = jax.jit(model.decode_tokens)

    full = model.init_cache(B, max_len)
    slots = {0: 6, 2: 9}  # slot -> prompt length (slot 1 stays parked)
    lasts = np.zeros((B,), np.int32)
    for slot, L in slots.items():
        prompt = jax.random.randint(jax.random.PRNGKey(slot), (1, L), 0, cfg.vocab_size)
        lg1, one = prefill(params, prompt, model.init_cache(1, max_len))
        full = admit(full, one, slot)
        lasts[slot] = int(jnp.argmax(lg1[0]))
    lens = np.asarray([slots.get(b, 1) for b in range(B)], np.int32)
    toks = np.tile(lasts[:, None], (1, Tn)).astype(np.int32)
    lg, _ = dtok(params, full, jnp.asarray(toks), jnp.asarray(lens), jnp.zeros((B,), jnp.int32))

    for slot, L in slots.items():
        prompt = jax.random.randint(jax.random.PRNGKey(slot), (1, L), 0, cfg.vocab_size)
        lg1, one = prefill(params, prompt, model.init_cache(1, max_len))
        t1 = jnp.tile(jnp.argmax(lg1, -1).astype(jnp.int32)[:, None], (1, Tn))
        lgi, _ = dtok(params, one, t1, jnp.asarray([L], jnp.int32), jnp.zeros((1,), jnp.int32))
        np.testing.assert_allclose(
            np.asarray(lg[slot]), np.asarray(lgi[0]), rtol=1e-5, atol=1e-5
        )
