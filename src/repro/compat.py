"""The one mesh / shard_map import surface of the repo (DESIGN.md §12).

The installed jax is 0.9.0, which offers ``jax.set_mesh``,
``jax.shard_map`` and ``jax.sharding.get_abstract_mesh`` publicly.  This
module wraps them once, so the model stack and the geo engine's sharded
strategies never touch mesh-context symbols themselves (GeoLint's
``compat-boundary`` rule enforces that, DESIGN.md §17):

  * ``use_mesh(mesh)`` — context manager activating ``mesh``: it enters
    ``jax.set_mesh`` and also records the *concrete* mesh in a
    context-local **ambient mesh** (a ``ContextVar``, so it nests and is
    async/thread-safe) — ``NamedSharding`` construction needs the
    device-backed mesh, which the abstract context mesh does not carry.
  * ``get_abstract_mesh()`` — the active mesh or ``None``: the ambient
    concrete mesh, else the abstract mesh of a raw ``jax.set_mesh``
    scope (the empty mesh normalized to ``None``).
  * ``shard_map(f, mesh=..., in_specs=..., out_specs=..., check_vma=...)``
    — ``jax.shard_map``; ``mesh=None`` resolves the active mesh and is a
    loud error outside every mesh scope.
  * ``with_sharding_constraint(x, spec, mesh=None)`` — a concrete
    ``Mesh`` is wrapped into a ``NamedSharding``; an abstract mesh uses
    the bare ``PartitionSpec``, which resolves against it inside jit.
"""
from __future__ import annotations

import contextlib
from contextvars import ContextVar
from typing import Optional

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

# ------------------------------------------------------------- ambient mesh
_ambient_mesh: ContextVar[Optional[Mesh]] = ContextVar(
    "repro_ambient_mesh", default=None)


def get_abstract_mesh():
    """The active mesh, or None when no mesh scope is in effect.

    The ambient ContextVar (recorded by :func:`use_mesh`) is consulted
    first; then ``jax.sharding.get_abstract_mesh()`` for scopes opened by
    a raw ``jax.set_mesh`` that bypassed ``use_mesh`` (the empty mesh
    normalizes to None).
    """
    m = _ambient_mesh.get()
    if m is not None:
        return m
    m = jax.sharding.get_abstract_mesh()
    if m is not None and m.axis_names:
        return m
    return None


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Context manager activating ``mesh`` for everything underneath it:
    ``jax.set_mesh`` plus the ambient concrete mesh (queried by
    ``models.layers.shard_act``, ``shard_map(mesh=None)`` and
    :func:`concrete_mesh`)."""
    token = _ambient_mesh.set(mesh)
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _ambient_mesh.reset(token)


def concrete_mesh() -> Optional[Mesh]:
    """The active *concrete* ``Mesh`` (device-backed), or None.

    ``NamedSharding`` construction (checkpoint restore, param shardings)
    needs real devices, which the abstract context mesh does not carry —
    hence ``use_mesh`` recording the concrete mesh.
    """
    return _ambient_mesh.get()


# ----------------------------------------------------------------- shard_map
def shard_map(f, *, mesh=None, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map``; ``mesh=None`` resolves the active mesh."""
    if mesh is None:
        mesh = get_abstract_mesh()
        if mesh is None:
            raise ValueError(
                "shard_map: no mesh argument and no ambient mesh active "
                "(wrap the call in repro.compat.use_mesh(...))")
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


# ------------------------------------------------------------------ builders
def make_mesh(shape, axes) -> Mesh:
    """jax.make_mesh with explicit Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


# --------------------------------------------------------------- constraints
def with_sharding_constraint(x, spec: PartitionSpec, mesh=None):
    """Activation-sharding constraint under the active (or given) mesh.

    ``mesh=None`` resolves the active mesh; no active mesh makes this a
    no-op (CPU smoke tests).  A concrete ``Mesh`` becomes a
    ``NamedSharding``; an abstract mesh takes the bare
    ``PartitionSpec``, which resolves against it inside jit.
    """
    if mesh is None:
        mesh = get_abstract_mesh()
    if mesh is None:
        return x
    if isinstance(mesh, Mesh):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    return jax.lax.with_sharding_constraint(x, spec)
