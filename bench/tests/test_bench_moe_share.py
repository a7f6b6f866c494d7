"""The reference's MoE and its weight draws at tiny sizes on the CPU: the
scanned expert loop against the loop over every expert it replaced, a share of
the experts (``num_experts`` held of ``reduced_from.num_experts``) against
the uncut layer, draws that compose, a compiled program whose size does
not grow with the number of experts, and the published-key check of the
program's router width, held count and share."""
from __future__ import annotations

import copy
import dataclasses
import hashlib
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, weights as W
from bench.kernels import model
from bench.reference import qwen3
from bench.tests import tiny
from repro import configs
from repro.configs import ModelConfig

SEED = 2**31 + 11
DATA = Path(__file__).resolve().parent / "data"


def moe_hf(experts=8, top_k=2, *, held=None, share=0, norm=True):
    """The tiny MoE file with ``experts`` behind the router; with ``held``,
    this chip holds share ``share`` of them."""
    hf = tiny.config(tiny.MOE, dtype="float32", held=held, share=share)
    hf.update(num_experts_per_tok=top_k, norm_topk_prob=norm)
    if held is None:
        hf["num_experts"] = experts
    else:
        hf["reduced_from"]["num_experts"] = experts
    return hf


def every_expert(x, w, hf, quant, chunk=2048):
    """The layer as it was written before the scan: a Python loop over every
    held expert, each token weighted by its gate (zero off its top k),
    inside a map over chunks of tokens.  (Inside a loop XLA divides by the
    fp8 scale in another way than outside one, which moves values that lie
    on an fp8 rounding tie by a step; so the oracle keeps the map.)"""
    T, d = x.shape
    dims = W.dims_of(hf)
    held = dims["experts"]
    probs = jax.nn.softmax(x @ w["moe/router"], axis=-1)
    top_w, top_e = jax.lax.top_k(probs, dims["top_k"])
    if hf["norm_topk_prob"]:
        top_w = top_w / top_w.sum(-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], top_e].set(top_w)
    gate = gate[:, dims["expert0"]:dims["expert0"] + held]

    def one_chunk(args):
        xc, gc = args
        y = jnp.zeros_like(xc)
        for e in range(held):
            y = y + gc[:, e:e + 1] * qwen3.swiglu(
                xc, w["moe/w_gate"][e], w["moe/w_up"][e], w["moe/w_down"][e], quant)
        return y

    pad = (-T) % chunk
    xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, chunk, d)
    gp = jnp.pad(gate, ((0, pad), (0, 0))).reshape(-1, chunk, held)
    return jax.lax.map(one_chunk, (xp, gp)).reshape(-1, d)[:T]


def layer(hf, layer=0):
    return W.reference_layer(W.seed_key(SEED), W.dims_of(hf), layer)


def tokens(hf, n=40):
    return jax.random.normal(jax.random.PRNGKey(5), (n, hf["hidden_size"]), jnp.float32)


def rel(a, b):
    return float(jnp.abs(a - b).max() / jnp.abs(b).max())


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("norm", [True, False])
def test_scanned_moe_matches_the_loop_over_every_expert(norm, quant):
    hf = moe_hf(norm=norm)
    w, x = layer(hf), tokens(hf)
    with jax.default_matmul_precision("highest"):
        got = qwen3.moe(x, w, hf, quant, chunk=16)   # 40 tokens: a padded last chunk
        want = every_expert(x, w, hf, quant, chunk=16)
    assert rel(got, want) < 1e-5


@pytest.mark.parametrize("quant", [None, "fp8"])
def test_shares_add_up_to_the_uncut_layer(quant):
    """8 experts, top-2: the four shares of 2 add up to the whole layer."""
    full = moe_hf()
    x = tokens(full)
    with jax.default_matmul_precision("highest"):
        want = qwen3.moe(x, layer(full), full, quant)
        parts = []
        for s in range(4):
            hf = moe_hf(held=2, share=s)
            got = qwen3.moe(x, layer(hf), hf, quant)
            assert rel(got, every_expert(x, layer(hf), hf, quant)) < 1e-5
            parts.append(got)
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
    assert rel(sum(parts), want) < 1e-5


def test_expert_draws_are_slices_of_the_uncut_layer():
    full = layer(moe_hf(), layer=1)
    for s in range(4):
        part = layer(moe_hf(held=2, share=s), layer=1)
        assert np.array_equal(part["moe/router"], full["moe/router"])
        for name in W.EXPERTS:
            assert np.array_equal(part[name], full[name][2 * s:2 * s + 2]), (s, name)
        for name in set(full) - {"moe/router", *W.EXPERTS}:
            assert np.array_equal(part[name], full[name]), name


def test_program_params_fill_the_held_experts_by_id():
    """The program's stacked expert leaves, for a chip holding experts 4-5,
    are the uncut layer's rows 4-5, super-block by super-block."""
    hf = moe_hf(held=2, share=2)
    dims = W.dims_of(hf)
    d, ff = dims["d"], dims["ff"]
    abstract = {"blocks": {"scan": {"b0": {"moe": {
        "router": jax.ShapeDtypeStruct((2, d, 8), jnp.float32),
        "w_gate": jax.ShapeDtypeStruct((2, 2, d, ff), jnp.bfloat16),
        "w_down": jax.ShapeDtypeStruct((2, 2, ff, d), jnp.bfloat16)}}}}}
    got = jax.jit(lambda k: W.program_params(k, abstract, dims, 1))(W.seed_key(SEED))
    full = W.dims_of(moe_hf())
    moe = got["blocks"]["scan"]["b0"]["moe"]

    def draw(name, layer, shape):   # under jit, as the reference draws
        return jax.jit(lambda k: W.draw(k, name, layer, shape, full))(W.seed_key(SEED))

    for sb in range(2):
        for name, shape in (("w_gate", (8, d, ff)), ("w_down", (8, ff, d))):
            assert np.array_equal(moe[name][sb], draw(f"moe/{name}", sb, shape)[4:6])
        want = draw("moe/router", sb, (d, 8)).astype(jnp.float32)
        assert np.array_equal(moe["router"][sb], want)


# sha256 (first 16 hex digits) of each bfloat16 draw at SEED, the tiny sizes,
# layer 1 (the top tensors: outside layers), recorded from the draws before
# experts were drawn by id; every tensor but an expert stack keeps its draw
PARENT_DRAWS = {
    "ln1": "62d32be65958e2d4", "ln2": "cd43ce0296a08442",
    "attn/wq": "87a15137a059ee21", "attn/wk": "ac207e0e1b5c9683",
    "attn/wv": "e9da578ec69aaac6", "attn/wo": "86e0d17d084d0e67",
    "attn/q_norm": "08d81721f546c4b9", "attn/k_norm": "673d0dd002f89f2b",
    "ffn/w_gate": "70c7a1876cb4dd44", "ffn/w_up": "5e9d1886d132026b",
    "ffn/w_down": "4062bb03a3c8e120", "moe/router": "83512a0938d6fb1d",
    "embed": "c0f1142c8701af02", "unembed": "8d50225952a0df3b",
    "final_norm": "ca72eb40d5fdf9d5",
}
# the tiny dense reference's first three logits at positions 20 and 21 of
# its first sequence, from the same recording
PARENT_LOGITS = [-0.07068776339292526, -0.5205082297325134, -0.044141121208667755,
                 0.10913398116827011, -0.3529838025569916, 0.012385447509586811]


def test_dense_draws_and_reference_match_the_recording():
    key = W.seed_key(SEED)
    seen = {}
    for name in ("qwen3-32b-1chip", tiny.MOE):
        dims = W.dims_of(tiny.config(name, dtype="float32"))
        top = W.top_shapes(dims)
        for n, shape in {**W.layer_shapes(dims), **top}.items():
            if n in W.EXPERTS:
                continue
            x = np.asarray(W.draw(key, n, -1 if n in top else 1, shape, dims))
            seen[n] = hashlib.sha256(x.view(np.uint16).tobytes()).hexdigest()[:16]
    assert seen == PARENT_DRAWS
    hf = tiny.config("qwen3-32b-1chip", dtype="float32")
    toks = np.random.default_rng(0).integers(0, 256, size=(2, 24), dtype=np.int32)
    got = qwen3.logits_at(hf, SEED, list(toks), [np.arange(20, 24)] * 2, pad_to=8)
    np.testing.assert_allclose(got[0][:2, :3].ravel(), PARENT_LOGITS, rtol=1e-6, atol=1e-7)


def _dot_generals(experts):
    hf = moe_hf(experts=experts)
    dims = W.dims_of(hf)
    w = {n: jax.ShapeDtypeStruct(s, jnp.float32) for n, s in W.layer_shapes(dims).items()}
    x = jax.ShapeDtypeStruct((1, 64, dims["d"]), jnp.float32)
    text = jax.jit(lambda x, w: qwen3.block(x, w, hf, None)).lower(x, w).as_text()
    return text.count("dot_general")


def test_reference_block_is_one_program_whatever_the_expert_count():
    n8 = _dot_generals(8)
    assert n8 > 3 and n8 == _dot_generals(128)


def share_file():
    return json.loads((DATA / "qwen3-235b-a22b.16of128.json").read_text())


def test_program_config_takes_a_share_at_the_router_width():
    hf = share_file()
    cfg = run.program_config(hf)          # the program's 128 experts: the router
    assert cfg.num_experts == 128 and W.dims_of(hf)["experts"] == 16
    bad = copy.deepcopy(hf)               # a router over only the 16 held
    bad["program"]["overrides"]["num_experts"] = 16
    with pytest.raises(SystemExit):
        run.program_config(bad)
    bad = copy.deepcopy(hf)
    bad["reduced_from"]["num_experts"] = 64
    with pytest.raises(SystemExit):
        run.program_config(bad)


# the program's config with the two fields by which a program says which
# experts it holds, whether or not its own config has them
HeldConfig = dataclasses.make_dataclass(
    "HeldConfig", [("experts_held", int, 0), ("expert_share", int, 0)],
    bases=(ModelConfig,), frozen=True)


@pytest.fixture
def holding_program(monkeypatch):
    program = configs.get_config

    def get(name):
        base = program(name)
        return HeldConfig(**{f.name: getattr(base, f.name) for f in dataclasses.fields(base)})

    monkeypatch.setattr(configs, "get_config", get)


def test_program_config_takes_the_programs_held_count_and_share(holding_program):
    cfg = run.program_config(share_file())
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_share) == (128, 16, 0)
    hf = tiny.config(tiny.MOE, held=2, share=1)
    cfg = run.program_config(hf)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_share) == (8, 2, 1)
    assert W.dims_of(hf)["expert0"] == 2


@pytest.mark.parametrize("override, named", [
    ({"experts_held": 0}, ["num_experts=16", "experts_held=0", "num_experts=128"]),
    ({"experts_held": 128}, ["num_experts=16", "experts_held=128"]),
    ({"expert_share": 1}, ["expert_share=0", "expert_share=1"]),
])
def test_program_config_refuses_another_held_count_or_share(holding_program, override, named):
    hf = share_file()
    hf["program"]["overrides"].update(override)
    with pytest.raises(SystemExit) as err:
        run.program_config(hf)
    assert all(n in str(err.value) for n in named), str(err.value)


@pytest.mark.parametrize("name", ["qwen3-235b-a22b.16of128", "qwen3-30b-a3b.6l"])
def test_dims_of_a_share(name):
    hf = json.loads((DATA / f"{name}.json").read_text())
    dims = W.dims_of(hf)
    router = hf["reduced_from"].get("num_experts", hf["num_experts"])
    assert dims["router"] == router == 128
    assert dims["expert0"] == hf.get("expert_share", 0) * dims["experts"]
    shapes = W.layer_shapes(dims)
    assert shapes["moe/router"] == (hf["hidden_size"], 128)
    assert shapes["moe/w_gate"][0] == hf["num_experts"]
    # the model's operations: the router at 128, the share of the routed FFN
    d, ff, k = hf["hidden_size"], hf["moe_intermediate_size"], hf["num_experts_per_tok"]
    routed = model._fixed(dims) - model._fixed(dict(dims, experts=0, ff=0))
    assert routed == pytest.approx(2 * d * 128 + 6 * d * ff * k * dims["experts"] / 128)


@pytest.mark.parametrize("share", [-1, 4])
def test_a_share_outside_the_router_is_refused(share):
    with pytest.raises(ValueError):
        W.dims_of(moe_hf(held=2, share=share))
