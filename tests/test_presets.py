"""The chip presets of qwen3-moe-235b-a22b, checked by shape alone
(``jax.eval_shape``: no device, no weights): widths as published, every cut
listed, and the bytes a chip holds within its 16 GB."""
from __future__ import annotations

import math

import jax
import pytest
from jax.sharding import AbstractMesh, PartitionSpec

from repro.configs import get_config
from repro.configs import qwen3_moe_235b_a22b as qwen
from repro.launch.steps import _abstract_params
from repro.parallel.sharding import param_pspecs

WIDTHS = ("d_model", "num_heads", "num_kv_heads", "head_dim", "d_ff_expert", "top_k",
          "qk_norm", "block_pattern", "route_mode", "rope_theta", "tie_embeddings")


def _bytes_per_chip(cfg, mesh_shape=None) -> int:
    params = _abstract_params(cfg)
    if mesh_shape is None:
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    mesh = AbstractMesh(tuple(mesh_shape.values()), tuple(mesh_shape))
    specs = param_pspecs(params, mesh)
    total = 0
    for leaf, spec in zip(jax.tree.leaves(params), jax.tree.leaves(
            specs, is_leaf=lambda s: isinstance(s, PartitionSpec))):
        split = math.prod(mesh_shape[a] for ax in spec if ax for a in
                          ((ax,) if isinstance(ax, str) else ax))
        total += leaf.size * leaf.dtype.itemsize // split
    return total


@pytest.mark.parametrize("name,reduced", [
    ("qwen3-moe-235b-a22b-1chip", qwen.ONE_CHIP_REDUCED),
    ("qwen3-moe-235b-a22b-4chip", qwen.FOUR_CHIP_REDUCED),
])
def test_chip_preset_keeps_published_widths_and_lists_every_cut(name, reduced):
    cfg, full = get_config(name), qwen.FULL
    for w in WIDTHS:
        assert getattr(cfg, w) == getattr(full, w), w
    changed = {f for f in full.__dataclass_fields__ if f != "name"
               and getattr(cfg, f) != getattr(full, f)}
    assert changed == set(reduced), (changed, set(reduced))


def test_one_chip_preset_fits_one_chip():
    cfg = get_config("qwen3-moe-235b-a22b-1chip")
    assert cfg.num_experts == 16 and cfg.num_layers == 4
    assert _bytes_per_chip(cfg) < 4e9
    # 4 layers x 2 (K, V) x 4 KV heads x 128 x 2 bytes
    assert cfg.num_layers * 2 * cfg.num_kv_heads * cfg.resolved_head_dim * 2 == 8192


def test_four_chip_preset_fits_each_chip_at_model_4():
    cfg = get_config("qwen3-moe-235b-a22b-4chip")
    assert cfg.num_experts == qwen.FULL.num_experts
    whole = _bytes_per_chip(cfg)
    per_chip = _bytes_per_chip(cfg, {"data": 1, "model": 4})
    assert whole > 16e9  # no single chip holds it
    assert per_chip < 16e9
