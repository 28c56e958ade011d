"""Mesh-aware LACIN collectives, flat and hierarchical.

:class:`LacinCollectives` binds the paper's 1-factor step schedules to a
``jax.sharding.Mesh``: every axis size is read from the mesh (or, when no
mesh is bound, statically from the axis environment inside ``shard_map``),
so the schedule can never disagree with the mesh shape — the
``axis_size=`` threading of the old API and its silent-mismatch foot-gun
are gone.

On top of the single-axis matching chains from
:mod:`repro.core.collectives`, two *hierarchical* schedules express what
the flat API cannot:

* :func:`all_to_all_grid` — personalized all-to-all over a HyperX-shaped
  mesh (a Cartesian product of CINs, paper §5): one LACIN schedule per
  mesh dimension, composed dimension-order.  A ``(K_a, K_b, ...)`` mesh
  runs ``sum_d (K_d - 1)`` matching steps instead of ``prod_d K_d - 1``,
  and every step stays inside one dimension's CIN rows — exactly the
  traffic the per-dimension 1-factors carry on the physical HyperX.
* :func:`all_reduce_two_level` — two-level Dragonfly all-reduce: local
  reduce-scatter (inside the group's CIN) -> global all-reduce of the
  scattered shards (one flow per group pair on the global CIN) -> local
  all-gather.  Global traffic is ``1/a`` of a flat all-reduce's.

Both are validated bit-for-bit against ``lax`` references in
``tests/test_fabric_collectives.py``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.collectives import (all_gather_lacin, all_reduce_lacin,
                                    all_to_all_lacin, reduce_scatter_lacin)
from repro.core.schedule import LacinSchedule, make_schedule


# ---------------------------------------------------------------------------
# Hierarchical schedules (free functions; sizes explicit).
# ---------------------------------------------------------------------------

def all_to_all_grid(x: jax.Array, axis_names: Sequence[str],
                    axis_sizes: Sequence[int] | None = None, *,
                    instance: str | Sequence[str] = "auto") -> jax.Array:
    """Personalized all-to-all over the product of ``axis_names``.

    ``x`` has leading dim ``prod(axis_sizes)``; ``x[j]`` is this device's
    chunk for device ``j``, with ``j`` the row-major index over the named
    axes (the same device order ``lax.all_to_all`` uses for a tuple of
    axis names).  Composed dimension-order: one LACIN matching schedule
    per mesh axis, innermost axis first.  Each stage exchanges only along
    one axis, so on a HyperX fabric every step rides that dimension's
    1-factors.  ``instance`` may be a single name or one per axis.
    """
    names = tuple(axis_names)
    if axis_sizes is None:
        sizes = tuple(jax.lax.axis_size(a) for a in names)
    else:
        sizes = tuple(int(s) for s in axis_sizes)
    insts = ((instance,) * len(names) if isinstance(instance, str)
             else tuple(instance))
    if len(insts) != len(names):
        raise ValueError(f"got {len(insts)} instances for {len(names)} axes")
    total = math.prod(sizes)
    if x.shape[0] != total:
        raise ValueError(f"leading dim {x.shape[0]} != prod{sizes} = {total}")
    rest = x.shape[1:]
    x = x.reshape(sizes + rest)          # per-axis destination coordinates
    for d in reversed(range(len(names))):
        x = jnp.moveaxis(x, d, 0)
        x = all_to_all_lacin(x, names[d], axis_size=sizes[d],
                             instance=insts[d])
        x = jnp.moveaxis(x, 0, d)        # coord d now indexes the *source*
    return x.reshape((total,) + rest)


def all_reduce_two_level(x: jax.Array, local_axis: str, global_axis: str, *,
                         local_size: int | None = None,
                         global_size: int | None = None,
                         local_instance: str = "auto",
                         global_instance: str = "auto") -> jax.Array:
    """Two-level Dragonfly all-reduce (sum) over ``local_axis x global_axis``.

    Local reduce-scatter -> global all-reduce of the 1/a-sized shards ->
    local all-gather.  Equals ``lax.psum(x, (local_axis, global_axis))``;
    2(a-1) local + 2(g-1) global matching steps, with every global step
    carrying shards of ``1/a`` of the payload — the l-g-l locality the
    paper's Dragonfly composition provides.
    """
    a = local_size if local_size is not None else jax.lax.axis_size(local_axis)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.size) % a
    if pad:
        flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(a, -1)
    shard = reduce_scatter_lacin(chunks, local_axis, axis_size=a,
                                 instance=local_instance)
    shard = all_reduce_lacin(shard, global_axis, axis_size=global_size,
                             instance=global_instance)
    full = all_gather_lacin(shard, local_axis, axis_size=a,
                            instance=local_instance)
    flat = full.reshape(-1)
    if pad:
        flat = flat[:-pad]
    return flat.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# The mesh-bound front-end.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LacinCollectives:
    """LACIN collectives bound to a mesh: axis sizes come from the mesh.

    ``mesh=None`` is allowed — sizes are then read statically from the
    bound axis environment inside ``shard_map``.  ``instance`` picks the
    schedule per axis (``'auto'`` = XOR for power-of-two sizes, else
    Circle); ``axis_instances`` overrides it per axis name (how
    ``DragonflyFabric`` binds its local/global instances).  ``impl='xla'``
    makes :meth:`psum` fall back to ``lax.psum`` for A/B comparisons.
    Obtain one via ``fabric.collectives(mesh, ...)`` to also get the
    fabric-vs-mesh shape check.
    """
    mesh: object | None = None
    instance: str = "auto"
    impl: str = "lacin"
    axis_instances: tuple[tuple[str, str], ...] = ()

    # -- mesh introspection --------------------------------------------------
    def axis_size(self, axis_name: str) -> int:
        if self.mesh is not None:
            if axis_name not in self.mesh.shape:
                raise ValueError(
                    f"bound mesh has no axis {axis_name!r} (axes: "
                    f"{tuple(self.mesh.axis_names)})")
            return int(self.mesh.shape[axis_name])
        return jax.lax.axis_size(axis_name)

    def axis_instance(self, axis_name: str) -> str:
        return dict(self.axis_instances).get(axis_name, self.instance)

    def schedule(self, axis_name: str) -> LacinSchedule:
        """The static step schedule this object uses on ``axis_name``."""
        return make_schedule(self.axis_instance(axis_name),
                             self.axis_size(axis_name))

    # -- flat (single-axis) collectives --------------------------------------
    def all_to_all(self, x, axis_name: str):
        return all_to_all_lacin(x, axis_name,
                                axis_size=self.axis_size(axis_name),
                                instance=self.axis_instance(axis_name))

    def all_gather(self, x, axis_name: str, *, tiled: bool = False):
        return all_gather_lacin(x, axis_name,
                                axis_size=self.axis_size(axis_name),
                                instance=self.axis_instance(axis_name),
                                tiled=tiled)

    def reduce_scatter(self, x, axis_name: str):
        return reduce_scatter_lacin(x, axis_name,
                                    axis_size=self.axis_size(axis_name),
                                    instance=self.axis_instance(axis_name))

    def all_reduce(self, x, axis_name: str):
        return all_reduce_lacin(x, axis_name,
                                axis_size=self.axis_size(axis_name),
                                instance=self.axis_instance(axis_name))

    def psum(self, x, axis_name: str):
        """All-reduce; ``impl='xla'`` defers to the compiler's psum."""
        if self.impl == "xla":
            return lax.psum(x, axis_name)
        return self.all_reduce(x, axis_name)

    def tree_all_reduce(self, tree, axis_name: str):
        """All-reduce every pytree leaf (DP gradient reduction)."""
        return jax.tree_util.tree_map(
            lambda g: self.all_reduce(g, axis_name), tree)

    # -- hierarchical collectives ---------------------------------------------
    def all_to_all_grid(self, x, axis_names: Sequence[str]):
        """Multi-axis dimension-order all-to-all (HyperX-shaped mesh)."""
        names = tuple(axis_names)
        return all_to_all_grid(
            x, names, tuple(self.axis_size(a) for a in names),
            instance=tuple(self.axis_instance(a) for a in names))

    def all_reduce_two_level(self, x, local_axis: str, global_axis: str):
        """Two-level Dragonfly all-reduce (local RS -> global AR -> local AG)."""
        return all_reduce_two_level(
            x, local_axis, global_axis,
            local_size=self.axis_size(local_axis),
            global_size=self.axis_size(global_axis),
            local_instance=self.axis_instance(local_axis),
            global_instance=self.axis_instance(global_axis))
