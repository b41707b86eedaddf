"""The canonical recursive jaxpr walker.

One walker for the whole repo.  Tests and rules used to carry divergent
hand-rolled copies (``tests/test_subtraction.py``'s ``_iter_eqns``,
``tests/test_goss.py``'s ``_prim_names``, ``tests/test_dist_goss.py``'s
inline ``prim_names``) that each handled a different subset of the
sub-jaxpr containers jax uses.  This module handles them all, generically:
a sub-jaxpr can hide in any ``eqn.params`` value as a ``Jaxpr``, a
``ClosedJaxpr``, or arbitrarily nested inside lists / tuples / dicts —
which covers ``jit``, ``scan``, ``while`` (cond + body), ``cond``
(branch list), ``custom_jvp_call`` / ``custom_vjp_call``, ``shard_map``,
``pallas_call`` (``grid_mapping`` holds the kernel jaxpr), ``remat``,
and whatever jax adds next, without naming any of them.
"""
from __future__ import annotations

from typing import Any, Iterator

__all__ = ["iter_eqns", "prim_names", "collect_avals", "sub_jaxprs"]


def _as_jaxpr(obj: Any):
    """Return the open ``Jaxpr`` held by ``obj``, or None.

    Duck-typed on purpose, so the walker imports no private jax module."""
    name = type(obj).__name__
    if name == "ClosedJaxpr":
        return obj.jaxpr
    if name == "Jaxpr":
        return obj
    return None


def sub_jaxprs(eqn) -> Iterator[Any]:
    """Every sub-jaxpr reachable from ``eqn.params``, in deterministic
    order (params sorted by key, containers walked front-to-back)."""
    stack = [eqn.params[k] for k in sorted(eqn.params, reverse=True)]
    while stack:
        v = stack.pop()
        j = _as_jaxpr(v)
        if j is not None:
            yield j
        elif isinstance(v, (list, tuple)):
            stack.extend(reversed(v))
        elif isinstance(v, dict):
            stack.extend(v[k] for k in sorted(v, reverse=True))


def iter_eqns(jaxpr, *, enter_pallas: bool = True) -> Iterator[Any]:
    """Yield every equation of ``jaxpr``, recursing into all sub-jaxprs.

    ``jaxpr`` may be a ``Jaxpr`` or ``ClosedJaxpr``.  With
    ``enter_pallas=False`` the ``pallas_call`` equation itself is still
    yielded but its kernel body is not entered — the right setting for
    rules about the XLA program *around* a kernel (in-kernel ops are the
    point of a fusion, and in-kernel collectives have different
    semantics than XLA collectives)."""
    j = _as_jaxpr(jaxpr)
    if j is None:
        raise TypeError(f"not a jaxpr: {type(jaxpr).__name__}")
    for eqn in j.eqns:
        yield eqn
        if not enter_pallas and eqn.primitive.name == "pallas_call":
            continue
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub, enter_pallas=enter_pallas)


# wrapper primitives whose *name* is trace plumbing, not computation —
# excluded from prim_names sequences so that "same primitives" comparisons
# are insensitive to how many jit boundaries wrap a function
TRANSPARENT_PRIMS = frozenset({"jit", "closed_call", "custom_jvp_call",
                               "custom_vjp_call", "remat", "remat2"})


def prim_names(jaxpr, *, transparent=TRANSPARENT_PRIMS,
               enter_pallas: bool = True) -> list[str]:
    """Flat primitive-name sequence of ``jaxpr``, recursing everywhere.

    Names in ``transparent`` are dropped from the sequence (their bodies
    are still walked), so a function and its ``jax.jit`` wrapping compare
    equal.  Pass ``transparent=()`` to keep every name."""
    return [e.primitive.name
            for e in iter_eqns(jaxpr, enter_pallas=enter_pallas)
            if e.primitive.name not in transparent]


def collect_avals(jaxpr, *, enter_pallas: bool = True) -> Iterator[Any]:
    """Every abstract value in the program: top-level invars/outvars plus
    each equation's in/out avals (sub-jaxprs included via iter_eqns).
    Literals contribute their avals too — a f64 constant is as much a
    dtype-policy violation as a f64 intermediate."""
    j = _as_jaxpr(jaxpr)
    seen_eqns = iter_eqns(j, enter_pallas=enter_pallas)
    for v in list(j.invars) + list(j.constvars):
        if hasattr(v, "aval"):
            yield v.aval
    for eqn in seen_eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            if hasattr(v, "aval"):
                yield v.aval
