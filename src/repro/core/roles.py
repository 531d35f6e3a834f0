"""Op-role annotations — the §3.5 triple-group taxonomy, machine-readable.

The paper groups table operations into three roles with different
commutativity properties (DESIGN.md §3):

  reader    pure probes — commute with each other and with updaters on
            disjoint or identical key sets; never move keys between slots.
  updater   in-place mutations of located entries (values/scores) — keys
            keep their (bucket, slot), so a locate computed before the op
            is still valid after it.
  inserter  ops that create, move, or destroy entries — serialization
            points: any locate computed before an inserter is invalid
            after it.

``OpSession`` uses the roles to share one locate across a run of commuting
ops and to fence at inserters.  hkv-lint's role checker
(``repro.analysis.roles``) requires every public op entry point in
``core/ops.py`` to carry one of these annotations and cross-checks the
session's recorded roles against them, so a new op cannot silently join
the session machinery with the wrong commutativity class.

Each annotated op also runs under the named scope ``hkv.op.<name>``, so
every instruction it stages carries the scope in its HLO ``op_name``
metadata: a device profile joined to the compiled module attributes the
op's own glue to the op layer (DESIGN.md §Observability).  A scope is
metadata only; the compiled program is otherwise unchanged.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, TypeVar

import jax

READER = "reader"
UPDATER = "updater"
INSERTER = "inserter"
ROLES = (READER, UPDATER, INSERTER)

_ATTR = "__hkv_role__"

F = TypeVar("F", bound=Callable)


def role(name: str) -> Callable[[F], F]:
    """Decorator declaring an op entry point's §3.5 role.

    ``@role(roles.READER)`` etc.  The op is wrapped (``functools.wraps``,
    so its signature and source stay visible) to run under the named scope
    ``hkv.op.<fn name>``; jit/static-argnum behaviour is untouched.
    """
    if name not in ROLES:
        raise ValueError(f"unknown op role {name!r}; expected one of {ROLES}")

    def mark(fn: F) -> F:
        op = scoped("hkv.op." + fn.__name__)(fn)
        setattr(op, _ATTR, name)
        return op

    return mark


def scoped(scope: str) -> Callable[[F], F]:
    """Decorator running a function under the named scope `scope`: one of
    the program's layers, ``hkv.closure`` (the batch closure) or
    ``hkv.kernel_io`` (the kernel wrappers), or ``hkv.emb.<method>``."""

    def wrap(fn: F) -> F:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(scope):
                return fn(*args, **kwargs)

        return inner

    return wrap


def reader(fn: F) -> F:
    return role(READER)(fn)


def updater(fn: F) -> F:
    return role(UPDATER)(fn)


def inserter(fn: F) -> F:
    return role(INSERTER)(fn)


def role_of(fn) -> Optional[str]:
    """The declared role of an op entry point, or None if unannotated.

    Sees through ``functools.partial``/``jax.jit`` wrappers exposing
    ``__wrapped__`` or ``func``.
    """
    seen = 0
    while fn is not None and seen < 8:
        r = getattr(fn, _ATTR, None)
        if r is not None:
            return r
        fn = getattr(fn, "__wrapped__", None) or getattr(fn, "func", None)
        seen += 1
    return None
