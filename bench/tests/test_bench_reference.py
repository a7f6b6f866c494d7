"""The plain reference against the program's full forward pass, at tiny
sizes on the CPU, in float32: the bench configurations' overrides (sync,
dropless routing) compute the published block."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run, weights as W
from bench.reference import qwen3
from bench.tests import tiny


@pytest.mark.parametrize("name", [tiny.MOE, "qwen3-32b-1chip"])
def test_reference_agrees_with_model_forward(name):
    from repro.models.model import Model
    from repro.models.transformer import init_params

    hf = tiny.config(name, dtype="float32")
    cfg = run.program_config(hf)
    dims = W.dims_of(hf)
    abstract = jax.eval_shape(lambda k: init_params(k, cfg), jax.ShapeDtypeStruct((2,), "uint32"))
    seed = 2**31 + 11
    params = jax.jit(lambda k: W.program_params(k, abstract, dims, len(cfg.block_pattern)))(
        W.seed_key(seed))
    toks = np.random.default_rng(0).integers(0, dims["vocab"], size=(2, 24), dtype=np.int32)
    got, _ = jax.jit(Model(cfg).forward)(params, jnp.asarray(toks))
    want = qwen3.logits_at(hf, seed, list(toks), [np.arange(24)] * 2, pad_to=8)
    got = np.asarray(got)
    scale = max(float(np.abs(w).max()) for w in want)
    err = max(float(np.abs(g - w).max()) for g, w in zip(got, want))
    assert err / scale < 1e-4, err / scale


def test_fake_fp8_keeps_three_mantissa_bits():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(64, 32)), jnp.float32)
    y = qwen3.fake_fp8(x, 0)
    assert np.allclose(np.abs(y).max(0), np.abs(x).max(0))
    rel = np.abs(np.asarray(y - x)) / np.maximum(np.abs(np.asarray(x)), 1e-3)
    big = np.abs(np.asarray(x)) > 0.1 * np.abs(np.asarray(x)).max(0)
    assert rel[big].max() <= 2.0 ** -4 + 1e-6
    assert rel[big].max() > 2.0 ** -8
