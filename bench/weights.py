"""Random served weights from ``--seed``: the part every architecture shares.

An architecture module (``archs/<name>.py``) names its tensors, their shapes
and scales; ``draw`` makes them all on the device in one jitted call from
one key, in the type each is served in. Matrices are drawn in the order of
their names, then the norm gains in the order given.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def key_of(seed: int):
    """A PRNG key from any non-negative whole number (seeds above 2**32 are
    folded in, not truncated)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


@partial(jax.jit, static_argnames=("mats", "gains"))
def draw(key, mats: tuple, gains: tuple) -> dict:
    """``mats``: ``(name, shape, std, dtype)`` of each tensor, drawn as
    N(0, std^2) in float32 and served in ``dtype``; ``gains``: ``(name,
    shape)`` of each norm gain, near 1 and kept in float32."""
    mats = sorted(mats)
    keys = jax.random.split(key, len(mats) + len(gains))
    out = {}
    for k, (name, shp, std, dtype) in zip(keys, mats):
        out[name] = (jax.random.normal(k, shp, jnp.float32) * std
                     ).astype(dtype)
    for k, (name, shp) in zip(keys[len(mats):], gains):
        out[name] = 1.0 + 0.1 * jax.random.normal(k, shp, jnp.float32)
    return out
