"""What every architecture's plain reference shares: the matrix product in
float32 at ``highest`` precision with the int8 control, and the logit gap
that the check compares. Nothing here is imported from the program under
test; the layer equations live in each ``archs/<name>.py``.

``quant="int8"`` is the control: every matrix product takes int8 weights
(symmetric, per output channel) and int8 activations (symmetric, per row),
the precision below the bf16 that the configurations state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _q8(x, axis):
    """Symmetric int8 quantise-dequantise along ``axis``'s reduction."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def mm(x, w, quant):
    """x (..., K) @ w (K, N) in float32 at highest precision."""
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


@jax.jit
def gaps(ref_logits, chosen):
    """How far below the reference's best logit each chosen token lies."""
    best = jnp.max(ref_logits, axis=-1)
    return best - jnp.take_along_axis(ref_logits, chosen[:, None], -1)[:, 0]
