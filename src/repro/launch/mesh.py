"""Production mesh definitions.

A FUNCTION, not a module constant — importing this module never touches jax
device state, so tests/benches keep their 1-CPU view and only dryrun.py
(which sets XLA_FLAGS first) ever builds the 256/512-device meshes.

Mesh shapes (assignment):
  single-pod : (16, 16)    axes ("data", "model")   = 256 chips (one v5e pod)
  multi-pod  : (2, 16, 16) axes ("pod", "data", "model") = 512 chips
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def abstract_mesh(shape, axes):
    """A device-free ``AbstractMesh`` of the given sizes and axis names."""
    return AbstractMesh(tuple(shape), tuple(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_serve_mesh(data: int = 0, model: int = 1):
    """Serving mesh (DESIGN.md §13): slot-DP over "data", optional TP over
    "model". ``data=0`` takes every available device onto the data axis —
    on the forced-host CI platform
    (``XLA_FLAGS=--xla_force_host_platform_device_count=4``) that is the
    4-way slot-DP mesh the sharded-serving parity gate runs on. A
    data-only mesh keeps per-row reduction order identical to the
    single-device program, which is what makes the token-exact parity
    contract of benchmarks/sharded_serving.py checkable."""
    n = len(jax.devices())
    if data <= 0:
        if n % model:
            raise ValueError(f"model={model} does not divide the "
                             f"{n}-device count; pass data= explicitly "
                             "to serve on a device subset")
        data = max(n // model, 1)
    if data * model > n:
        raise ValueError(f"mesh ({data}, {model}) needs {data * model} "
                         f"devices, have {n}")
    return _make_mesh((data, model), ("data", "model"))


def make_smoke_mesh(devices=None):
    """Smallest nontrivial mesh for CPU tests (requires >=4 host devices,
    set via XLA_FLAGS in the test process)."""
    n = len(devices or jax.devices())
    if n >= 8:
        shape, axes = (2, 4), ("data", "model")
    elif n >= 4:
        shape, axes = (2, 2), ("data", "model")
    else:
        shape, axes = (1, 1), ("data", "model")
    return _make_mesh(shape, axes)
