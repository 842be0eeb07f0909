"""Production mesh builders (TPU v5e target).

Single pod : (data=16, model=16)            = 256 chips
Multi-pod  : (pod=2, data=16, model=16)     = 512 chips

Functions, not module constants, so importing this module never touches
jax device state (the dry-run forces 512 host devices *before* any jax
initialization — see dryrun.py).

Every mesh is built by :func:`auto_mesh`, with ``Auto`` axis types:
``jax.make_mesh`` defaults to ``Explicit`` axes, which reject the
``with_sharding_constraint`` placement pins the round programs thread
(:mod:`repro.sharding.specs`).
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices):
    """``jax.make_mesh`` over ``devices`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — run via "
            "launch/dryrun.py which forces XLA_FLAGS host device count")
    return auto_mesh(shape, axes, devices[:n])


def make_local_mesh():
    """Degenerate 1x1 mesh for CPU tests/benchmarks."""
    return auto_mesh((1, 1), ("data", "model"), jax.devices()[:1])


def make_engine_mesh(shape, axes):
    """Mesh from the serializable ``ExperimentConfig.mesh_shape`` /
    ``mesh_axes`` knobs, laid over the first prod(shape) devices.

    Unlike the fixed production meshes above, this accepts any
    shape/axes pair (Engine experiments sweep device counts via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    shape = tuple(int(s) for s in shape)
    axes = tuple(axes)
    if len(shape) != len(axes):
        raise ValueError(f"mesh_shape {shape} and mesh_axes {axes} must "
                         "have equal length")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} before "
            "jax initializes (see benchmarks/bench_round.py --devices)")
    return auto_mesh(shape, axes, devices[:n])


def batch_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def cohort_size(mesh) -> int:
    n = 1
    for a in batch_axes(mesh):
        n *= mesh.shape[a]
    return n
