"""Sharding helpers that degrade gracefully outside a mesh context."""
from __future__ import annotations

from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def current_mesh() -> Optional[Mesh]:
    try:
        from jax._src import mesh as mesh_lib
        ctx = mesh_lib.thread_resources.env.physical_mesh
        if ctx is not None and not ctx.empty:
            return ctx
    except Exception:
        pass
    return None


def shard(x: Any, spec: P) -> Any:
    """with_sharding_constraint if a mesh is active, else identity."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def constrain(x: Any, mesh: Optional[Mesh], spec: P) -> Any:
    """with_sharding_constraint against an EXPLICIT mesh.

    Unlike ``shard`` this needs no ambient mesh context, so it works
    from any thread (the serving path is multi-threaded and cannot rely
    on the thread-local ``with mesh:`` scope).  ``mesh=None`` degrades
    to identity.
    """
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_spec(mesh: Optional[Mesh]) -> P:
    """PartitionSpec for the batch axis: ('pod','data') when present."""
    if mesh is None:
        return P()
    names = mesh.axis_names
    axes = tuple(a for a in ("pod", "data") if a in names)
    return P(axes if axes else None)


def batch_axes(mesh: Optional[Mesh]):
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
