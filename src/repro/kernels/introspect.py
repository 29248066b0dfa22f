"""Jaxpr introspection: count kernel launches a traced function would issue.

The flat-packed substrate's contract is that the whole-pytree LARS update
issues exactly TWO ``pallas_call`` launches per step regardless of how
many leaves the parameter pytree has. This module turns that contract
into something a test/benchmark can assert: trace the function and count
``pallas_call`` equations recursively through nested jaxprs (jit, scan,
cond, custom_vjp, ...).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
from jax.extend import core as jcore


def _count_in_jaxpr(jaxpr, name: str) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            n += 1
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                n += _count_in_jaxpr(sub, name)
    return n


def _subjaxprs(v: Any):
    if isinstance(v, jcore.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jcore.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _subjaxprs(item)


def count_primitive(fn: Callable, *args, primitive: str, **kwargs) -> int:
    """Trace ``fn(*args, **kwargs)`` and count ``primitive`` equations."""
    closed = jax.make_jaxpr(lambda *a, **kw: fn(*a, **kw))(*args, **kwargs)
    return _count_in_jaxpr(closed.jaxpr, primitive)


def count_pallas_launches(fn: Callable, *args, **kwargs) -> int:
    """Number of ``pallas_call`` launches one invocation of ``fn`` issues."""
    return count_primitive(fn, *args, primitive="pallas_call", **kwargs)
