"""The axis-name contract between models and launchers.

Models never name concrete mesh axes for the batch dimension; they annotate
activations with the :data:`BATCH` sentinel and ``constrain`` resolves it
against whatever mesh is active:

  * no active mesh (unit tests, single device)   -> no-op
  * inside ``shard_map`` (mesh axes are manual)  -> no-op (data already local)
  * under ``jax.set_mesh(mesh)``                 -> ``with_sharding_constraint``
    with axes filtered to the ones the mesh actually has.

This is what lets the same model code run unchanged on 1 device, an 8-fake-
device test mesh, and the 512-chip production mesh.
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

#: mesh axes a batch dimension may shard over, outermost first.
BATCH = ("pod", "data")


def active_mesh():
    """The mesh made current by ``with mesh:`` or ``jax.set_mesh``, else None."""
    try:  # classic pjit resource env (`with mesh:`)
        from jax._src import mesh as mesh_lib

        m = mesh_lib.thread_resources.env.physical_mesh
        if m is not None and not m.empty:
            return m
    except Exception:  # noqa: BLE001 - internal layout differs across versions
        pass
    m = jax.sharding.get_abstract_mesh()
    if m is not None and getattr(m, "axis_names", ()):
        return m
    return None


def manual_axis_names() -> set:
    """Axis names currently bound as manual/mapped (inside shard_map et al.)."""
    try:
        from jax._src.core import get_axis_env

        return set(get_axis_env().axis_sizes)
    except Exception:  # noqa: BLE001
        return set()


def current_abstract_mesh():
    """Mesh made current by ``jax.set_mesh`` / ``with mesh:``, else None."""
    return active_mesh()


def batch_axes() -> tuple[str, ...]:
    """The BATCH contract filtered to the active mesh's axes."""
    mesh = active_mesh()
    if mesh is None:
        return BATCH
    return tuple(a for a in BATCH if a in mesh.axis_names)


def _resolve(entry, avail: set, used: set):
    """One PartitionSpec entry: sentinel tuple / axis name / None."""
    if entry is None:
        return None
    if isinstance(entry, (tuple, list)):
        picked = tuple(a for a in entry if a in avail and a not in used)
        used.update(picked)
        return picked if picked else None
    if entry in avail and entry not in used:
        used.add(entry)
        return entry
    return None


def constrain(x, *spec):
    """``with_sharding_constraint`` iff a mesh is active and we are not inside
    a manual (shard_map) region.  ``spec`` entries are per-dimension: an axis
    name, a tuple of axis names (e.g. :data:`BATCH`), or None.  A spec whose
    length doesn't match ``x.ndim`` (e.g. the same helper called under vmap)
    is a no-op rather than an error."""
    mesh = active_mesh()
    if mesh is None or len(spec) != x.ndim:
        return x
    manual = manual_axis_names()
    if manual & set(mesh.axis_names):
        return x  # inside shard_map: shards are already local arrays
    avail = set(mesh.axis_names)
    used: set = set()
    pspec = P(*[_resolve(e, avail, used) for e in spec])
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, pspec))
