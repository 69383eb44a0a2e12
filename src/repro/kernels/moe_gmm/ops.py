"""jit'd public wrapper for the grouped matmul kernel (``interpret=True`` for the
Pallas interpreter; the default compiles for a TPU)."""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import moe_gmm as _kernel_call
from .ref import gmm_ref


def moe_gmm(x, w, group_sizes=None, *, bc: int = 128, bf: int = 128,
            bd: int = 128, interpret: bool = False):
    if group_sizes is None:
        group_sizes = jnp.full((x.shape[0],), x.shape[1], jnp.int32)
    return _kernel_call(x, w, group_sizes.astype(jnp.int32),
                        bc=bc, bf=bf, bd=bd, interpret=interpret)


__all__ = ["moe_gmm", "gmm_ref"]
