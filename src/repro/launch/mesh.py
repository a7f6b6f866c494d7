"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never initializes jax's device backend — required because the dry-run forces
512 host devices while tests/benchmarks must see 1.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    # Auto axes: the partitioner propagates shardings from the in/out specs
    # and ``with_sharding_constraint`` hints (jax.make_mesh defaults to
    # Explicit axes, which reject both)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The target deployment mesh.

    single pod:  (data=16, model=16)          = 256 chips (TPU v5e pod)
    multi-pod:   (pod=2, data=16, model=16)   = 512 chips

    ``pod`` composes with ``data`` for batch/gradient parallelism; its
    reduction hop crosses the (slow) inter-pod links, which is why the
    trainer's hierarchical reduction treats it separately.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]) -> Mesh:
    """Arbitrary mesh over available devices (tests: small host meshes)."""
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small (data, model) mesh over however many host devices exist."""
    return _auto_mesh((data, model), ("data", "model"))


def elastic_mesh(n_healthy: int, *, model: int = 16, multi_pod: bool = False) -> Mesh:
    """Elastic re-shape: rebuild the largest (data, model) mesh that fits the
    surviving device count, keeping the model axis fixed (parameter sharding
    must stay valid) and shrinking the data axis.  Used by runtime.elastic on
    (injected) node failures."""
    if n_healthy < model:
        raise ValueError(f"cannot keep model={model} with {n_healthy} devices")
    data = n_healthy // model
    devices = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devices, ("data", "model"))
