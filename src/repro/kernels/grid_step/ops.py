"""jit'd public wrapper for the grid stencil kernel (``interpret=True`` for the
Pallas interpreter; the default compiles for a TPU)."""
from __future__ import annotations

from .kernel import grid_step as _kernel_call
from .ref import grid_step_ref


def grid_step(labels, cond, *, band: int = 8, interpret: bool = False):
    return _kernel_call(labels, cond, band=band, interpret=interpret)


__all__ = ["grid_step", "grid_step_ref"]
