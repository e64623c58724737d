"""Production meshes.

Single pod: (data=16, model=16) — 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) — 512 chips across 2 pods; the 'pod'
axis is pure data parallelism (gradient all-reduce + sketch max-reduce cross
pod), 'model' stays intra-pod where ICI is fastest.

Functions, not module constants: importing this module must never touch jax
device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax


def make_auto_mesh(shape, axes):
    """jax.make_mesh with every axis explicitly Auto (sharding propagated
    by the compiler, as the shard_map-based serve path expects)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False, tp: int = 16):
    """tp != 16 is a §Perf variant: same 256 chips/pod, different DP x TP
    factorization (data = 256 // tp).  The assignment baseline is tp=16."""
    data = 256 // tp
    shape = (2, data, tp) if multi_pod else (data, tp)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_test_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many devices the test process has."""
    return make_auto_mesh(shape, axes)


def n_chips(mesh) -> int:
    return mesh.devices.size
