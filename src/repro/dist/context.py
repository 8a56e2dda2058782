"""Ambient sharding context: annotate tensors without threading (mesh, rules).

``use_sharding(mesh, rules)`` installs a thread-local (mesh, rules) pair for
the duration of a trace; ``constrain(x, *logical_axes)`` then resolves the
logical annotation against the ambient context and applies
``jax.lax.with_sharding_constraint``.  Outside any context — unit tests, CPU
smoke runs, eager debugging — ``constrain`` is a no-op, so model code carries
its sharding annotations unconditionally and stays runnable everywhere.
"""

from __future__ import annotations

import threading
from collections.abc import Iterator
from contextlib import contextmanager

import jax
from jax.sharding import Mesh, NamedSharding

from .sharding import ShardingRules, spec_for


__all__ = ["use_sharding", "current_sharding", "current_platform", "constrain"]

_STATE = threading.local()


@contextmanager
def use_sharding(mesh: Mesh, rules: ShardingRules) -> Iterator[None]:
    """Make (mesh, rules) the ambient sharding context; nestable."""
    previous = getattr(_STATE, "context", None)
    _STATE.context = (mesh, rules)
    try:
        yield
    finally:
        _STATE.context = previous


def current_sharding() -> tuple[Mesh, ShardingRules] | None:
    """The active (mesh, rules) pair, or ``None`` outside ``use_sharding``."""
    return getattr(_STATE, "context", None)


def current_platform() -> str:
    """The platform the code being traced runs on: that of the ambient mesh's
    devices, or the default backend's outside a context or for an abstract
    mesh.  A compile for a described TPU from a CPU process reads ``"tpu"``."""
    context = current_sharding()
    if context is not None and isinstance(context[0], Mesh):
        return context[0].devices.flat[0].platform
    return jax.default_backend()


def constrain(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Constrain ``x`` to the sharding its logical axes resolve to.

    One ``logical_axes`` entry per dimension of ``x`` (``None`` = replicated
    dimension).  A no-op when no sharding context is active.
    """
    context = current_sharding()
    if context is None:
        return x
    mesh, rules = context
    spec = spec_for(logical_axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
