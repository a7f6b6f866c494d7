"""Plain float32 reference of the published Qwen3 decoder, dense and MoE.

It follows the Qwen3 and Qwen3-MoE blocks of Hugging Face ``transformers``
(``modeling_qwen3.py``, ``modeling_qwen3_moe.py``), written out in
``jax.numpy`` with no kernel, cache or batching trick, and imports nothing
of the program under test:

    h   = x + o_proj(attention(rope(q_norm(q_proj(rms(x)))),
                               rope(k_norm(k_proj(rms(x)))), v_proj(rms(x))))
    out = h + ffn(rms(h))

with RMSNorm ``x / sqrt(mean(x^2) + eps) * w``, rotary embeddings on the
two halves of each head (``rotate_half``), grouped-query causal softmax
attention scaled by ``1/sqrt(head_dim)``, a SwiGLU FFN
``down(silu(gate(x)) * up(x))``, and for MoE a softmax router over the
experts, the top ``num_experts_per_tok`` kept and renormalised
(``norm_topk_prob``), every token served by its experts with none dropped.
Where the configuration holds a share of the experts (``num_experts`` held
of ``reduced_from.num_experts``, share ``expert_share``), the router keeps
its published width and the layer adds only the held experts' part.
Logits are ``rms(x) @ unembed^T``.  Every matrix product runs at
``jax.default_matmul_precision("highest")``.

Weights come from :mod:`bench.weights`, layer by layer, so the reference
holds one layer at a time; the sequences of a check run through each layer
together, padded at the end to a common length (causal attention keeps the
padding out of every real position).

``quant="fp8"`` is the control: every weight matrix and every activation
that enters a weight matrix product rounded to float8 e4m3, with a scale per
output channel (weights) or per token (activations); attention and the
router stay in float32.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

FP8_MAX = 448.0  # largest finite float8 e4m3fn


def fake_fp8(x: jax.Array, axis) -> jax.Array:
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, spec, quant, w_in_axes):
    """einsum of an activation with a weight, optionally through fp8."""
    if quant == "fp8":
        x = fake_fp8(x, -1)
        w = fake_fp8(w, w_in_axes)
    return jnp.einsum(spec, x, w)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (..., S, heads, hd); rotate_half on the two halves of each head."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]  # (S, hd/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(xn, w, hf, quant, q_block=128):
    """Causal GQA over (N, S, d) sequences, in blocks of queries."""
    N, S, _ = xn.shape
    nq, nkv, hd = hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"]
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    pos = jnp.arange(S)
    q = _mm(xn, w["attn/wq"], "nsd,dhk->nshk", quant, 0)
    k = _mm(xn, w["attn/wk"], "nsd,dhk->nshk", quant, 0)
    v = _mm(xn, w["attn/wv"], "nsd,dhk->nshk", quant, 0)
    q = rope(rms(q, w["attn/q_norm"], eps), pos, theta)
    k = rope(rms(k, w["attn/k_norm"], eps), pos, theta)
    g = nq // nkv
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    if S % q_block:
        q_block = S
    nb = S // q_block

    def one(qb_i):
        qb = jax.lax.dynamic_slice_in_dim(q, qb_i * q_block, q_block, axis=1)
        s = jnp.einsum("nqhk,nthk->nhqt", qb, k) / jnp.sqrt(jnp.float32(hd))
        qpos = qb_i * q_block + jnp.arange(q_block)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        return jnp.einsum("nhqt,nthk->nqhk", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(one, jnp.arange(nb))  # (nb, N, qb, nq, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(N, S, nq, hd)
    return _mm(o, w["attn/wo"], "nshk,hkd->nsd", quant, (0, 1))


def swiglu(x, wg, wu, wd, quant):
    g = _mm(x, wg, "td,df->tf", quant, 0)
    u = _mm(x, wu, "td,df->tf", quant, 0)
    return _mm(jax.nn.silu(g) * u, wd, "tf,fd->td", quant, 0)


def moe(x, w, hf, quant, chunk=2048):
    """The held experts' part of the MoE layer: the softmax router over all
    ``router`` experts, its top k kept and renormalised, and each token's
    gate-weighted SwiGLUs of the held experts it routes to, in expert order.
    With every expert held that is the whole layer.

    The experts are a ``lax.scan`` over their stacks, the gate column of
    each a scanned input, so the compiled program is one expert's, whatever
    the count; every token goes through every held expert."""
    T, d = x.shape
    dims = W.dims_of(hf)
    held, first = dims["experts"], dims["expert0"]
    probs = jax.nn.softmax(x @ w["moe/router"], axis=-1)  # (T, router)
    top_w, top_e = jax.lax.top_k(probs, dims["top_k"])
    if hf.get("norm_topk_prob", True):
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], top_e].set(top_w)
    gate = gate[:, first:first + held]
    stacks = (w["moe/w_gate"], w["moe/w_up"], w["moe/w_down"])

    def one_chunk(args):
        xc, gc = args  # gc (held, chunk): the gate columns

        def one_expert(y, e):
            wg, wu, wd, g = e
            return y + g[:, None] * swiglu(xc, wg, wu, wd, quant), None

        return jax.lax.scan(one_expert, jnp.zeros_like(xc), stacks + (gc,))[0]

    pad = (-T) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, chunk, d)
    gp = jnp.pad(gate, ((0, pad), (0, 0))).reshape(-1, chunk, held).transpose(0, 2, 1)
    return jax.lax.map(one_chunk, (xp, gp)).reshape(-1, d)[:T]


def block(x, w, hf, quant):
    eps = hf["rms_norm_eps"]
    h = x + attention(rms(x, w["ln1"], eps), w, hf, quant)
    hn = rms(h, w["ln2"], eps)
    N, S, d = hn.shape
    flat = hn.reshape(N * S, d)
    if "num_experts" in hf:
        y = moe(flat, w, hf, quant)
    else:
        y = swiglu(flat, w["ffn/w_gate"], w["ffn/w_up"], w["ffn/w_down"], quant)
    return h + y.reshape(N, S, d)


def head(x, top, hf, quant):
    xn = rms(x, top["final_norm"], hf["rms_norm_eps"])
    return _mm(xn, top["unembed"], "td,vd->tv", quant, 1)


def logits_at(hf: Dict, seed: int, seqs: Sequence[np.ndarray],
              positions: Sequence[np.ndarray], *, quant: Optional[str] = None,
              pad_to: int = 512) -> List[np.ndarray]:
    """Logits (len(positions[i]), vocab) at the given positions of each
    sequence, from the whole published model at ``seed``'s weights."""
    dims = W.dims_of(hf)
    key = W.seed_key(seed)
    S = max(len(s) for s in seqs)
    S = -(-S // pad_to) * pad_to
    toks = np.zeros((len(seqs), S), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    with jax.default_matmul_precision("highest"):
        top = jax.jit(lambda k: W.reference_top(k, dims))(key)
        x = jax.jit(lambda e, t: e[t])(top["embed"], jnp.asarray(toks))
        layer_w = jax.jit(lambda k, l: W.reference_layer(k, dims, l))
        step = jax.jit(lambda x, w: block(x, w, hf, quant))
        for l in range(dims["layers"]):
            w = layer_w(key, jnp.int32(l))
            x = step(x, w)
            del w
        out_fn = jax.jit(lambda x, top: head(x, top, hf, quant))
        out = []
        for i, p in enumerate(positions):
            rows = x[i, jnp.asarray(np.asarray(p, np.int32))]
            out.append(np.asarray(out_fn(rows, top)))
    return out
