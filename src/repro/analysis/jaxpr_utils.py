"""Shared jaxpr-walking plumbing for every analysis in this package.

All analyses operate on jaxprs obtained via ``jax.make_jaxpr`` — tracing
only, no lowering, no execution — so they are backend-independent and run on
the CPU CI host even for geometries that target TPU Mosaic or Triton.
"""

from __future__ import annotations

from typing import Any, Callable, Iterator

import jax

from jax.extend.core import ClosedJaxpr, Jaxpr, Var


def as_jaxpr(obj: Any) -> Jaxpr:
    """Accept a traced callable result, ClosedJaxpr, or Jaxpr uniformly."""
    if isinstance(obj, ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, Jaxpr):
        return obj
    raise TypeError(f"expected (Closed)Jaxpr, got {type(obj).__name__}")


def subjaxprs(val: Any) -> list[Jaxpr]:
    """Every jaxpr reachable from one eqn-param value (lists/tuples walked)."""
    if isinstance(val, ClosedJaxpr):
        return [val.jaxpr]
    if isinstance(val, Jaxpr):
        return [val]
    if isinstance(val, (list, tuple)):
        return [j for v in val for j in subjaxprs(v)]
    return []


def iter_eqns(jaxpr: Any) -> Iterator[Any]:
    """Depth-first over every eqn in ``jaxpr`` including all sub-jaxprs."""
    jx = as_jaxpr(jaxpr)
    for eqn in jx.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in subjaxprs(val):
                yield from iter_eqns(sub)


def trace(fn: Callable, *args: Any, **kwargs: Any) -> ClosedJaxpr:
    """Trace ``fn`` to a ClosedJaxpr without executing it."""
    return jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)


def eqns_by_primitive(jaxpr: Any, name: str) -> list[Any]:
    """All eqns (recursively) whose primitive is called ``name`` exactly."""
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == name]


def is_drop_var(v: Any) -> bool:
    """True for an unused eqn outvar (jaxpr prints it as ``_``)."""
    return type(v).__name__ == "DropVar"


def aval_elements(v: Any) -> int:
    """Element count of a var's abstract value (0 if shapeless)."""
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        if not isinstance(d, int):  # symbolic dim: treat as heavy
            return 1 << 30
        n *= d
    return n


__all__ = [
    "ClosedJaxpr",
    "Jaxpr",
    "Var",
    "as_jaxpr",
    "aval_elements",
    "eqns_by_primitive",
    "is_drop_var",
    "iter_eqns",
    "subjaxprs",
    "trace",
]
