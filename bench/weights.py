"""Seeded weights: one rule of draws, shared by the program's feed and the
reference, so that both compute with the same numbers and neither takes
them from the other.

Every tensor is drawn as ``std * normal`` in float32 from a key folded from
the run's seed, the tensor's name and its layer, then rounded to bfloat16,
the type the weights are served in.  The draw is the same whichever side
asks for it.  Two maps read it:

* ``published``: the tensor of the published block that the reference
  computes with, in float32.  RMSNorm weights are ``1 + draw``; the
  embedding table is ``sqrt(hidden_size) * draw``.
* the program's storage, for the feed: the draw itself.  The program keeps
  RMSNorm weights as offsets from 1 and multiplies embeddings by
  ``sqrt(d_model)`` after the lookup, so the draw is what it stores.

Layers are drawn one at a time, so the reference can hold one layer at once.

An MoE layer's router is drawn at its published width (``router``), and each
expert's ``w_gate``, ``w_up`` and ``w_down`` from a key that folds in the
expert's id, so that the stacks of a share of the experts are slices of the
uncut layer's.  A configuration holds a share as ``num_experts`` held of
``reduced_from.num_experts``, and ``expert_share`` s (default 0) says which:
ids ``[s * held, (s + 1) * held)``.
"""
from __future__ import annotations

import math
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "final_norm", "attn/q_norm", "attn/k_norm")
EXPERTS = ("moe/w_gate", "moe/w_up", "moe/w_down")  # stacks on axis 0, by expert id


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (the low and high words)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def dims_of(hf: Dict) -> Dict[str, int]:
    """The sizes the draws need, from a configuration file's published keys:
    ``experts`` held, of ``router`` (the router's width), from id ``expert0``."""
    moe = "num_experts" in hf
    held = hf.get("num_experts", 0)
    router = hf.get("reduced_from", {}).get("num_experts", held)
    share = hf.get("expert_share", 0)
    if moe and not (0 <= share and (share + 1) * held <= router):
        raise ValueError(f"expert_share {share} of {held} held experts lies outside "
                         f"the router's {router}")
    return {
        "d": hf["hidden_size"], "nq": hf["num_attention_heads"],
        "nkv": hf["num_key_value_heads"], "hd": hf["head_dim"],
        "layers": hf["num_hidden_layers"], "vocab": hf["vocab_size"],
        "ff": hf["moe_intermediate_size"] if moe else hf["intermediate_size"],
        "experts": held, "router": router, "expert0": share * held,
        "top_k": hf.get("num_experts_per_tok", 0),
    }


def _std(name: str, shape: Tuple[int, ...], dims: Dict[str, int]) -> float:
    two_l = 2 * dims["layers"]
    if name in NORMS:
        return 0.1
    if name in ("embed", "unembed", "moe/router"):
        return 0.02
    if name == "attn/wo":
        return 1.0 / math.sqrt(dims["nq"] * dims["hd"] * two_l)
    if name.endswith("w_down"):
        return 1.0 / math.sqrt(dims["ff"] * two_l)
    return 1.0 / math.sqrt(dims["d"])  # wq, wk, wv, w_gate, w_up: fan-in d


def draw(key: jax.Array, name: str, layer: int, shape: Tuple[int, ...],
         dims: Dict[str, int]) -> jax.Array:
    """The bfloat16 draw of tensor ``name`` of ``layer`` (-1: outside layers).
    An expert stack's rows are experts ``expert0``, ``expert0 + 1``, ...,
    each drawn from the tensor's key folded with its id."""
    k = jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(name.encode())), layer + 1)
    std = _std(name, shape, dims)
    if name in EXPERTS:
        ids = dims["expert0"] + jnp.arange(shape[0])
        one = lambda e: jax.random.normal(jax.random.fold_in(k, e), shape[1:], jnp.float32)
        x = jax.vmap(one)(ids) * std
    else:
        x = jax.random.normal(k, shape, jnp.float32) * std
    return x.astype(jnp.bfloat16)


def published(name: str, x: jax.Array, dims: Dict[str, int]) -> jax.Array:
    """The published block's float32 tensor for a draw."""
    x = x.astype(jnp.float32)
    if name in NORMS:
        return 1.0 + x
    if name == "embed":
        return x * math.sqrt(dims["d"])
    return x


def layer_shapes(dims: Dict[str, int]) -> Dict[str, Tuple[int, ...]]:
    d, nq, nkv, hd, ff, E = (dims[k] for k in ("d", "nq", "nkv", "hd", "ff", "experts"))
    shapes = {
        "ln1": (d,), "ln2": (d,),
        "attn/wq": (d, nq, hd), "attn/wk": (d, nkv, hd), "attn/wv": (d, nkv, hd),
        "attn/wo": (nq, hd, d), "attn/q_norm": (hd,), "attn/k_norm": (hd,),
    }
    if E:
        shapes.update({"moe/router": (d, dims["router"]), "moe/w_gate": (E, d, ff),
                       "moe/w_up": (E, d, ff), "moe/w_down": (E, ff, d)})
    else:
        shapes.update({"ffn/w_gate": (d, ff), "ffn/w_up": (d, ff), "ffn/w_down": (ff, d)})
    return shapes


def top_shapes(dims: Dict[str, int]) -> Dict[str, Tuple[int, ...]]:
    return {"embed": (dims["vocab"], dims["d"]), "unembed": (dims["vocab"], dims["d"]),
            "final_norm": (dims["d"],)}


def reference_layer(key: jax.Array, dims: Dict[str, int], layer: int) -> Dict[str, jax.Array]:
    """One layer's published float32 tensors."""
    return {n: published(n, draw(key, n, layer, s, dims), dims)
            for n, s in layer_shapes(dims).items()}


def reference_top(key: jax.Array, dims: Dict[str, int]) -> Dict[str, jax.Array]:
    return {n: published(n, draw(key, n, -1, s, dims), dims)
            for n, s in top_shapes(dims).items()}


# ---------------------------------------------------------------------------
# the program's parameter tree
# ---------------------------------------------------------------------------


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for p in path:
        out.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return tuple(out)


def program_params(key: jax.Array, abstract, dims: Dict[str, int], pattern_len: int):
    """The program's parameter tree filled with the draws, leaf by leaf in
    the program's dtypes.  ``abstract`` is the program's tree of shapes:
    ``blocks/scan/b<j>/...`` leaves stack super-blocks on axis 0,
    ``blocks/rest/<i>/...`` are the layers after them.  An expert stack is
    filled id by id from the held range, as :func:`draw` gives it.  Call
    under ``jit``."""
    n_sb = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract)[0]:
        names = _path_names(path)
        if names[:2] == ("blocks", "scan"):
            n_sb = leaf.shape[0]
            break

    def fill(path, leaf):
        names = _path_names(path)
        if names[0] != "blocks":
            x = draw(key, "/".join(names), -1, leaf.shape, dims)
        elif names[1] == "scan":
            j = int(names[2][1:])
            name = "/".join(names[3:])
            x = jnp.stack([draw(key, name, i * pattern_len + j, leaf.shape[1:], dims)
                           for i in range(leaf.shape[0])])
        else:
            name = "/".join(names[3:])
            x = draw(key, name, n_sb * pattern_len + int(names[2]), leaf.shape, dims)
        return x.astype(leaf.dtype)

    return jax.tree_util.tree_map_with_path(fill, abstract)
