"""Recursive jaxpr walking shared by the lint rules and the tests.

One walker for every consumer (the primitive-budget rule, the host-sync
lint, the dtype-promotion lint, and ``tests/test_paged_prefill``'s
zero-gather acceptance) so the tests and the lint can never drift
apart. The walk descends into every sub-jaxpr a primitive carries in
its params — ``pjit``'s inner jaxpr, ``scan``/``while``/``cond``
bodies, ``custom_vjp``/``custom_jvp`` branches, and Pallas kernel
bodies alike — whether the param value is a ``ClosedJaxpr``, a raw
``Jaxpr``, or a list/tuple of either.
"""

from __future__ import annotations

from collections import Counter
from typing import Iterator

from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = ["subjaxprs", "iter_eqns", "count_primitive", "primitive_counts"]


def _as_jaxpr(obj) -> Jaxpr:
    """Normalize ClosedJaxpr / make_jaxpr output / raw Jaxpr to Jaxpr."""
    inner = getattr(obj, "jaxpr", None)
    if inner is not None:
        return _as_jaxpr(inner)
    return obj


def subjaxprs(eqn) -> Iterator[Jaxpr]:
    """Every sub-jaxpr referenced by one equation's params."""
    for val in eqn.params.values():
        for sub in val if isinstance(val, (list, tuple)) else (val,):
            if isinstance(sub, ClosedJaxpr):
                yield sub.jaxpr
            elif isinstance(sub, Jaxpr):
                yield sub


def iter_eqns(jaxpr, *, path: tuple[str, ...] = ()) -> Iterator[tuple[tuple[str, ...], "object"]]:
    """Yield ``(path, eqn)`` for every equation in the jaxpr tree.

    ``path`` is the tuple of enclosing primitive names (e.g.
    ``("pjit", "scan")``), so findings can say *where* a flagged
    primitive lives, not just that it exists.
    """
    jaxpr = _as_jaxpr(jaxpr)
    for eqn in jaxpr.eqns:
        yield path, eqn
        sub_path = path + (eqn.primitive.name,)
        for sub in subjaxprs(eqn):
            yield from iter_eqns(sub, path=sub_path)


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of a primitive anywhere in a (closed) jaxpr tree."""
    return sum(1 for _, eqn in iter_eqns(jaxpr) if eqn.primitive.name == name)


def primitive_counts(jaxpr) -> Counter:
    """Counter of every primitive name in the jaxpr tree."""
    return Counter(eqn.primitive.name for _, eqn in iter_eqns(jaxpr))
