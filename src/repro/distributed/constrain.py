"""Activation sharding constraints (mesh-aware, no-op off-mesh).

GSPMD propagation alone can resolve sharding ambiguities the wrong way
(e.g. un-sharding the batch at the embedding gather). Production JAX
frameworks pin activations at a few load-bearing points; these helpers do
that *without* the models knowing about meshes: if no mesh is active
(CPU smoke tests), they are identity.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import AbstractMesh, PartitionSpec as P


def current_mesh() -> Optional[AbstractMesh]:
    """The ambient mesh, or None.

    Entry points that trace a mesh-aware step do so inside
    ``jax.set_mesh(mesh)``; the constraints below (and the packed
    substrate's, see ``packing.constrain_rows``) see the mesh only
    there, and are identity without it."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def _batch_axes(mesh: AbstractMesh, n: int):
    """(pod, data) prefix that divides n, else None."""
    ba = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    size = math.prod(mesh.shape[a] for a in ba) if ba else 1
    return ba if ba and n % size == 0 else None


def shard_batch(x, *, last: Optional[str] = None):
    """Constrain dim0 to the batch axes; optionally dim -1 to ``last``."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = [None] * x.ndim
    spec[0] = _batch_axes(mesh, x.shape[0])
    if last is not None and last in mesh.axis_names \
            and x.shape[-1] % mesh.shape[last] == 0:
        spec[-1] = last
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_spec(x, *axes):
    """Constrain to an explicit per-dim axis tuple (names or None),
    dropping axes that don't exist in the current mesh or don't divide."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = []
    for dim, a in zip(x.shape, axes):
        if a is None:
            spec.append(None)
            continue
        names = (a,) if isinstance(a, str) else tuple(a)
        names = tuple(n for n in names if n in mesh.axis_names)
        size = math.prod(mesh.shape[n] for n in names) if names else 1
        spec.append(names if names and dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(x, P(*spec))
