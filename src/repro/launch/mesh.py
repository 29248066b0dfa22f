"""Mesh construction: every mesh in the repo is built here.

FUNCTIONS, not module-level constants, so importing this module never
touches jax device state.

All axes are ``AxisType.Auto``: the step functions place data with
``in_shardings``/``with_sharding_constraint`` and leave propagation to
GSPMD, which is what ``jax.make_mesh``'s Explicit default would refuse.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` with Auto axis types over ``devices`` (default:
    the leading ``prod(shape)`` local devices)."""
    n = 1
    for s in shape:
        n *= s
    devs = list(devices) if devices is not None else jax.devices()[:n]
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devs)


def mesh_from_spec(spec: str, *, allow_none: bool = False):
    """Shared ``--mesh`` CLI parsing (train + serve launchers).

    ``DATAxMODEL`` (e.g. ``4x2``) -> explicit (data, model) mesh over
    the leading D*M devices; ``auto`` -> all local devices on the data
    axis; ``none`` (serve: single-device engine) -> None when
    ``allow_none``.
    """
    if allow_none and spec == "none":
        return None
    ndev = len(jax.devices())
    if spec == "auto":
        return make_mesh((ndev, 1), ("data", "model"))
    try:
        data, model = (int(s) for s in spec.lower().split("x"))
    except ValueError:
        choices = "'auto'" + (", 'none'" if allow_none else "")
        raise SystemExit(f"--mesh expects {choices} or DATAxMODEL, "
                         f"got {spec!r}")
    if data * model > ndev:
        raise SystemExit(f"--mesh {spec} needs {data * model} devices, "
                         f"have {ndev}")
    return make_mesh((data, model), ("data", "model"))
