"""Plain reference of a llama-layout decoder.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
cache, no pages, no batching, no kernels, and nothing imported from the
program under test. It follows the published model code (HF
``LlamaModel``): pre-norm blocks with RMSNorm, rotary position embedding in
the rotate-half layout, grouped-query causal attention and a SwiGLU MLP,
then a final RMSNorm and the LM head. The weights it reads are the served
checkpoint (``weights.py``), taken to float32 layer by layer.

``quant="int8"`` is the control: every matrix product takes int8 weights
(symmetric, per output channel) and int8 activations (symmetric, per row),
the precision below the bf16 that the configuration states.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _q8(x, axis):
    """Symmetric int8 quantise-dequantise along ``axis``'s reduction."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, quant):
    """x (..., K) @ w (K, N) in float32 at highest precision."""
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x (S, H, D), positions 0..S-1; HF's rotate-half layout."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv              # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps",
                                   "theta", "quant"))
def _layer(x, lw, *, heads, kv_heads, head_dim, eps, theta, quant):
    s = x.shape[0]
    h = _rms(x, lw["norm1"], eps)
    q = _rope(_mm(h, lw["wq"], quant).reshape(s, heads, head_dim), theta)
    k = _rope(_mm(h, lw["wk"], quant).reshape(s, kv_heads, head_dim), theta)
    v = _mm(h, lw["wv"], quant).reshape(s, kv_heads, head_dim)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)
    x = x + _mm(o.reshape(s, heads * head_dim), lw["wo"], quant)
    h = _rms(x, lw["norm2"], eps)
    m = jax.nn.silu(_mm(h, lw["w_gate"], quant)) * _mm(h, lw["w_up"], quant)
    return x + _mm(m, lw["w_down"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(x, g, table, *, eps, quant):
    return _mm(_rms(x, g, eps), table.T, quant)


def logits(w: dict, c: dict, tokens, quant=None):
    """(S, V) float32 logits of every position of ``tokens`` (S,), by the
    published forward pass. ``quant="int8"`` gives the control."""
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    table = w["embed"].astype(F32)
    x = table[jnp.asarray(tokens)]
    for layer in range(c["num_hidden_layers"]):
        lw = {k: w[k][layer].astype(F32)
              for k in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                        "norm1", "norm2")}
        x = _layer(x, lw, heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   eps=eps, theta=theta, quant=quant)
    head = table if c["tie_word_embeddings"] else w["head"].astype(F32)
    return _logits(x, w["final_norm"].astype(F32), head, eps=eps,
                   quant=quant)


@jax.jit
def gaps(ref_logits, chosen):
    """How far below the reference's best logit each chosen token lies."""
    best = jnp.max(ref_logits, axis=-1)
    return best - jnp.take_along_axis(ref_logits, chosen[:, None], -1)[:, 0]
