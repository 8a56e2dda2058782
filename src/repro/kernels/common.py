"""Shared Pallas utilities: platform dispatch + compiler params."""

from __future__ import annotations

from collections.abc import Sequence

from ..dist.context import current_platform

__all__ = ["default_interpret", "tpu_compiler_params"]


def default_interpret(interpret: bool | None) -> bool:
    """Pallas TPU kernels run in interpret mode off a TPU.

    On a CPU backend interpret=True executes the kernel body with jnp
    semantics (correctness validation); on a TPU the same code lowers through
    Mosaic.  The platform is the ambient mesh's
    (:func:`repro.dist.context.current_platform`), so a compile for a
    described TPU lowers the kernels as the chip runs them.
    """
    if interpret is not None:
        return interpret
    return current_platform() != "tpu"


def tpu_compiler_params(
    dimension_semantics: Sequence[str], interpret: bool, vmem_limit_bytes: int | None = None
):
    """CompilerParams with dimension semantics and, where given, a scoped VMEM
    limit other than the chip's default (None in interpret mode)."""
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=tuple(dimension_semantics), vmem_limit_bytes=vmem_limit_bytes
    )
