"""Random served weights from ``--seed``, and the program's view of them.

One jitted call makes every matrix on the device, in bf16 (the type the
configurations state), from one key: the served checkpoint, in a llama
layout.

``program_params`` arranges the same arrays into the tree the program's
``Engine`` takes. It reshapes nothing and copies no matrix: only the small
norm gains are re-expressed, because the program applies a gain ``g`` as
``(1 + g)``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def shapes(c: dict) -> dict:
    L, d, f = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    s = {"embed": (c["vocab_size"], d), "wq": (L, d, q), "wk": (L, d, kv),
         "wv": (L, d, kv), "wo": (L, q, d), "w_gate": (L, d, f),
         "w_up": (L, d, f), "w_down": (L, f, d)}
    if not c["tie_word_embeddings"]:
        s["head"] = s["embed"]
    return s


def key_of(seed: int):
    """A PRNG key from any non-negative whole number (seeds above 2**32 are
    folded in, not truncated)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


@partial(jax.jit, static_argnames=("cfg_items",))
def _make(key, cfg_items):
    c = dict(cfg_items)
    L, d = c["num_hidden_layers"], c["hidden_size"]
    out = {}
    names = sorted(shapes(c))
    keys = jax.random.split(key, len(names) + 3)
    for k, name in zip(keys, names):
        shp = shapes(c)[name]
        # matrices ~ N(0, 1/fan_in); embedding ~ N(0, 0.02^2)
        std = 0.02 if name in ("embed", "head") else shp[-2] ** -0.5
        out[name] = (jax.random.normal(k, shp, jnp.float32) * std
                     ).astype(jnp.bfloat16)
    # norm gains near 1, kept in float32 as the program keeps them
    for k, name, shp in zip(keys[len(names):], ("norm1", "norm2", "final_norm"),
                            ((L, d), (L, d), (d,))):
        out[name] = 1.0 + 0.1 * jax.random.normal(k, shp, jnp.float32)
    return out


def make(c: dict, seed: int) -> dict:
    """The served weights of configuration ``c`` drawn from ``seed``."""
    items = tuple(sorted((k, v) for k, v in c.items()
                         if isinstance(v, (int, float, bool, str))))
    return _make(key_of(seed), items)


@jax.jit
def _gain(g):
    return g - 1.0


def program_params(w: dict) -> dict:
    """The program's parameter tree over the same device arrays."""
    block = {"norm1": {"scale": _gain(w["norm1"])},
             "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
             "norm2": {"scale": _gain(w["norm2"])},
             "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}
    p = {"embed": w["embed"], "stack": [[block]],
         "final_norm": {"scale": _gain(w["final_norm"])}}
    if "head" in w:
        p["head"] = w["head"]
    return p
