"""The one spelling of Pallas TPU compiler parameters the kernels share.

Every ``pallas_call`` in ``repro.kernels`` declares its grid's dimension
semantics through :func:`tpu_compiler_params`, so a change in how the
installed JAX spells Mosaic's options is a one-line fix here.
"""

from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu


def tpu_compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic compiler parameters declaring each grid axis ``"parallel"``
    (disjoint output blocks) or ``"arbitrary"`` (sequential, in order)."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


__all__ = ["tpu_compiler_params"]
