"""Shared Pallas utilities: platform dispatch + compiler params."""

from __future__ import annotations

from collections.abc import Sequence

import jax

__all__ = ["default_interpret", "tpu_compiler_params"]


def default_interpret(interpret: bool | None) -> bool:
    """Pallas TPU kernels run in interpret mode on non-TPU backends.

    On a CPU backend interpret=True executes the kernel body with jnp
    semantics (correctness validation); on a TPU the same code lowers through
    Mosaic.
    """
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def tpu_compiler_params(dimension_semantics: Sequence[str], interpret: bool):
    """CompilerParams with dimension semantics (None in interpret mode)."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(dimension_semantics=tuple(dimension_semantics))
