"""Mesh-aware internal sharding constraints.

`constrain(x, ...dims)` applies jax.lax.with_sharding_constraint when traced
under a mesh (the `with jax.set_mesh(mesh):` context) that defines the named
axes, and is a no-op otherwise (so model code runs unchanged in single-device tests).
Dim tokens:

    "batch"  -> all data-parallel axes present (("pod","data") or ("data",))
    "model"  -> the tensor-parallel axis
    None     -> replicated
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def _active_mesh():
    """The mesh in scope during tracing (set with ``jax.set_mesh``), or
    None."""
    am = jax.sharding.get_abstract_mesh()
    return am if am.axis_names else None


def axis_size(name: str) -> int:
    """Size of a mesh axis in the active mesh, or 1 if absent."""
    mesh = _active_mesh()
    if mesh is None or name not in (mesh.axis_names or ()):
        return 1
    return mesh.shape[name]


def dp_size() -> int:
    """Combined size of the data-parallel axes (pod x data)."""
    return axis_size("pod") * axis_size("data")


def constrain(x, *dims):
    mesh = _active_mesh()
    if mesh is None:
        return x
    names = tuple(mesh.axis_names or ())
    if not names:
        return x
    spec = []
    for d in dims:
        if d == "batch":
            ax = tuple(a for a in ("pod", "data") if a in names)
            spec.append(ax if len(ax) > 1 else (ax[0] if ax else None))
        elif d == "all":  # every mesh axis (context-parallel long sequences)
            ax = tuple(a for a in ("pod", "data", "model") if a in names)
            spec.append(ax if len(ax) > 1 else (ax[0] if ax else None))
        elif d is not None and d in names:
            spec.append(d)
        else:
            spec.append(None)
    # drop axes that don't divide the dim (mirror sharding.fit_spec)
    fixed = []
    for dim_size, ax in zip(x.shape, spec):
        if ax is None:
            fixed.append(None)
            continue
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= mesh.shape[a]
        fixed.append(ax if dim_size % size == 0 else None)
    try:
        return jax.lax.with_sharding_constraint(x, P(*fixed))
    except Exception:
        return x
