"""Collective helpers: int8 gradient compression fidelity and the
hierarchical grad sync (subprocess, 8 host devices)."""
from __future__ import annotations

import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.collectives import dequantize_int8, quantize_int8, tree_bytes
from tests.conftest import run_subprocess_devices


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 64)) * 3.0, jnp.float32)
    q, scale = quantize_int8(x)
    y = dequantize_int8(q, scale)
    # symmetric int8: error bounded by half a quantization step
    assert float(jnp.abs(x - y).max()) <= float(scale) * 0.5 + 1e-7
    assert q.dtype == jnp.int8


def test_quantize_zero_tensor():
    q, scale = quantize_int8(jnp.zeros((8,)))
    assert float(scale) == 1.0 and not q.any()


def test_tree_bytes():
    t = {"a": jnp.zeros((4, 4), jnp.float32), "b": jnp.zeros((2,), jnp.int8)}
    assert tree_bytes(t) == 4 * 4 * 4 + 2


def test_hierarchical_sync_with_compression():
    """2-'pod' x 4-'data' host mesh: compressed hierarchical psum approximates
    the exact mean within int8 tolerance, at 1/4 the inter-pod bytes."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.parallel.collectives import hierarchical_grad_sync

        mesh = make_mesh((2, 4), ("pod", "data"))
        rng = np.random.default_rng(0)
        g_all = rng.standard_normal((8, 32)).astype(np.float32)

        @partial(jax.shard_map, mesh=mesh, in_specs=P(("pod", "data")), out_specs=P(("pod", "data")))
        def sync(g):
            out = hierarchical_grad_sync(
                {"g": g}, intra_axes=("data",), inter_axis="pod",
                compress_inter=True, mean=True,
                axis_sizes={"data": 4, "pod": 2},
            )
            return out["g"]

        got = sync(jnp.asarray(g_all))
        want = g_all.mean(axis=0, keepdims=True)
        err = np.abs(np.asarray(got) - want).max()
        scale = np.abs(g_all).max() / 127
        assert err < 4 * scale + 1e-6, (err, scale)
        print("OK", err)
    """)
    out = run_subprocess_devices(code, n_devices=8)
    assert "OK" in out
