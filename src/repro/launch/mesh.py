"""Production mesh construction.

Single pod: (16, 16) = 256 chips, axes (data, model).
Multi-pod:  (2, 16, 16) = 512 chips, axes (pod, data, model) — the ``pod``
axis carries only data-parallel traffic (gradient/EMA all-reduce), so
scaling out = adding pods; see DESIGN.md §5.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests run on 1 CPU device; only
launch/dryrun.py forces 512 host devices).
"""
from __future__ import annotations

import jax


def make_mesh_auto(shape, axes):
    """jax.make_mesh with every axis of type Auto."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_auto(shape, axes)


def make_serving_mesh(n_devices: int | None = None):
    """1-D ("shard",) mesh for the sharded serving subsystem.

    Cluster shards AND the request batch both partition over this single
    axis (stage 1 is cluster-parallel, stage 4 batch-parallel — see
    serving/sharding.py).  Defaults to every visible device; tests force
    8 host-platform devices via XLA_FLAGS (scripts/test.sh multi-device
    tier).
    """
    n = n_devices or len(jax.devices())
    return make_mesh_auto((n,), ("shard",))


def make_debug_mesh(n_devices: int | None = None):
    """Tiny mesh over whatever devices exist (tests)."""
    n = n_devices or len(jax.devices())
    if n >= 4:
        return make_mesh_auto((n // 2, 2), ("data", "model"))
    return make_mesh_auto((n, 1), ("data", "model"))


def mesh_axes(mesh) -> tuple:
    return tuple(mesh.axis_names)


def batch_axes_of(mesh) -> tuple:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
