"""A llama-layout decoder: pre-norm blocks with RMSNorm, rotary position
embedding in the rotate-half layout, grouped-query causal attention and a
SwiGLU MLP, then a final RMSNorm and the LM head (HF ``LlamaModel``).

An architecture module is everything of the benchmark that knows one
architecture's tensors and equations. A configuration file names its
module by path from the repository root under ``"bench_arch"``; the harness
loads it once and reaches the model only through it. Each module defines:

``program_config(m)``
    The program's ``ModelConfig`` for configuration file ``m``, every size
    set from the file, so the file is what runs.
``make_weights(m, seed)``
    The served checkpoint, drawn on the device from ``seed``
    (``weights.draw``), as a dict of named arrays.
``program_params(w)``
    The program's parameter tree over the same arrays: the engine serves
    exactly the checkpoint the reference reads.
``engine_kwargs(w)``
    Keyword arguments the program's ``Engine`` takes besides the tree (an
    MoE stack's router bias); ``{}`` where there are none.
``logits(w, m, tokens, quant=None)``
    ``(S, V)`` float32 logits of every position of ``tokens`` by the plain
    reference at ``highest`` precision, importing nothing of the program;
    ``quant="int8"`` is the control (``reference.mm``). It may compute in
    blocks of positions or layers: the harness calls it once per checked
    request, at the engine's ``max_cache`` length.
``work(m)``
    The required work, read by the metric readers as ``run.model``:
    ``param_bytes``, ``kv_bytes_per_token``, ``token_flops(context,
    with_head)``, ``prefill_flops(start, count, last)`` and
    ``decode_tick(step)`` -> ``(FLOPs, bytes)`` for one ``harness.Step``.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

import weights
from reference import F32, mm

MATS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


# ---------------------------------------------------------------------------
# the program
# ---------------------------------------------------------------------------
def program_config(m: dict):
    """The program's own entry for ``m["arch"]`` with every size set from
    the file."""
    from repro import configs
    if m["hidden_act"] != "silu":
        raise ValueError(f"unsupported activation {m['hidden_act']!r}")
    return dataclasses.replace(
        configs.get_config(m["arch"]), num_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], num_heads=m["num_attention_heads"],
        num_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        tie_embeddings=m["tie_word_embeddings"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        act="silu", dtype=m["torch_dtype"])


# ---------------------------------------------------------------------------
# the served weights
# ---------------------------------------------------------------------------
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


def gain_shapes(c: dict) -> tuple:
    L, d = c["num_hidden_layers"], c["hidden_size"]
    return (("norm1", (L, d)), ("norm2", (L, d)), ("final_norm", (d,)))


def std(name: str, shape: tuple) -> float:
    """Matrices ~ N(0, 1/fan_in); the embedding and head ~ N(0, 0.02^2)."""
    return 0.02 if name in ("embed", "head") else shape[-2] ** -0.5


def make_weights(c: dict, seed: int) -> dict:
    """Every matrix in the configuration's type; norm gains near 1 in
    float32, as the program keeps them."""
    dt = jnp.dtype(c["torch_dtype"])
    mats = tuple((n, s, std(n, s), dt) for n, s in shapes(c).items())
    return weights.draw(weights.key_of(seed), mats, gain_shapes(c))


@jax.jit
def _gain(g):
    return g - 1.0


def block(w: dict) -> dict:
    """One scanned block of the program's tree. It reshapes nothing and
    copies no matrix: only the small norm gains are re-expressed, because
    the program applies a gain ``g`` as ``(1 + g)``."""
    return {"norm1": {"scale": _gain(w["norm1"])},
            "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": {"scale": _gain(w["norm2"])},
            "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")}}


def program_params(w: dict, blk=block) -> dict:
    """The program's parameter tree over the same device arrays, with
    ``blk(w)`` as the one scanned block."""
    p = {"embed": w["embed"], "stack": [[blk(w)]],
         "final_norm": {"scale": _gain(w["final_norm"])}}
    if "head" in w:
        p["head"] = w["head"]
    return p


def engine_kwargs(w: dict) -> dict:
    return {}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------
def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, theta):
    """x (S, H, D), positions 0..S-1; HF's rotate-half layout."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv              # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attend(x, lw, *, heads, kv_heads, head_dim, eps, theta, quant):
    """The residual stream after the attention half of a block."""
    s = x.shape[0]
    h = rms(x, lw["norm1"], eps)
    q = rope(mm(h, lw["wq"], quant).reshape(s, heads, head_dim), theta)
    k = rope(mm(h, lw["wk"], quant).reshape(s, kv_heads, head_dim), theta)
    v = mm(h, lw["wv"], quant).reshape(s, kv_heads, head_dim)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) / math.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)
    return x + mm(o.reshape(s, heads * head_dim), lw["wo"], quant)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps",
                                   "theta", "quant"))
def _layer(x, lw, *, heads, kv_heads, head_dim, eps, theta, quant):
    x = attend(x, lw, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
               eps=eps, theta=theta, quant=quant)
    h = rms(x, lw["norm2"], eps)
    m = jax.nn.silu(mm(h, lw["w_gate"], quant)) * mm(h, lw["w_up"], quant)
    return x + mm(m, lw["w_down"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _logits(x, g, table, *, eps, quant):
    return mm(rms(x, g, eps), table.T, quant)


def logits(w: dict, c: dict, tokens, quant=None, layer=_layer,
           names=MATS + ("norm1", "norm2")):
    """(S, V) float32 logits of every position of ``tokens`` (S,), by the
    published forward pass, one layer at a time: each layer's weights
    ``names`` are taken to float32 and passed through ``layer``.
    ``quant="int8"`` gives the control."""
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    table = w["embed"].astype(F32)
    x = table[jnp.asarray(tokens)]
    for i in range(c["num_hidden_layers"]):
        lw = {k: w[k][i].astype(F32) for k in names}
        x = layer(x, lw, heads=c["num_attention_heads"],
                  kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                  eps=eps, theta=theta, quant=quant)
    head = table if c["tie_word_embeddings"] else w["head"].astype(F32)
    return _logits(x, w["final_norm"].astype(F32), head, eps=eps,
                   quant=quant)


# ---------------------------------------------------------------------------
# the required work
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ModelWork:
    """Required work counted from the shapes: what the model needs, never
    what an implementation moves. Parameters are read once per decode tick,
    each emitting lane reads its own live K/V and writes one position of
    it, and attention covers each lane's real context. Padded lanes,
    inactive slots, gathered block tables and recomputed tokens are not the
    model's work and are never passed in."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    weight_bytes: int          # the matrices' type, as the file states
    norm_bytes: int = 4        # norm gains are kept in float32

    @classmethod
    def from_config(cls, c: dict) -> "ModelWork":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tied=c["tie_word_embeddings"],
                   weight_bytes=jnp.dtype(c["torch_dtype"]).itemsize)

    @property
    def attn_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return d * self.heads * hd * 2 + d * self.kv_heads * hd * 2

    @property
    def layer_matmul_params(self) -> int:
        """Matrix parameters of one layer that each token multiplies."""
        return self.attn_params + 3 * self.d_model * self.d_ff

    @property
    def head_params(self) -> int:
        return self.vocab * self.d_model

    @property
    def param_bytes(self) -> int:
        """Every stored parameter once: matrices, embedding (and head when
        untied), and the 2L+1 norm gains."""
        mats = self.layers * self.layer_matmul_params + self.head_params
        if not self.tied:
            mats += self.head_params
        norms = (2 * self.layers + 1) * self.d_model
        return mats * self.weight_bytes + norms * self.norm_bytes

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim \
            * self.weight_bytes

    def attn_flops(self, context: int) -> int:
        """QK^T and PV of one query position over ``context`` positions."""
        return 4 * self.layers * self.heads * self.head_dim * context

    def token_flops(self, context: int, with_head: bool) -> int:
        """One token through every layer attending ``context`` positions
        (itself included), plus the LM head when its logits are needed."""
        f = 2 * self.layers * self.layer_matmul_params + self.attn_flops(context)
        return f + (2 * self.head_params if with_head else 0)

    def decode_tick(self, step) -> tuple[int, int]:
        """(FLOPs, bytes) one decode tick requires. ``step.decode`` lists,
        per lane that emits a token this tick, the positions it attends (its
        input token included)."""
        contexts = step.decode
        flops = sum(self.token_flops(c, True) for c in contexts)
        # c - 1 positions of live K/V are read and the input token's one
        # position is written
        nbytes = self.param_bytes + self.kv_bytes_per_token * sum(contexts)
        return flops, nbytes

    def prefill_flops(self, start: int, count: int, last: bool) -> int:
        """Prompt positions ``start .. start+count-1`` computed, each
        attending its own prefix; ``last`` adds the head for the position
        that seeds decoding."""
        n = count
        ctx_sum = n * start + n * (n + 1) // 2
        f = 2 * self.layers * self.layer_matmul_params * n \
            + self.attn_flops(1) * ctx_sum
        return f + (2 * self.head_params if last else 0)


def work(c: dict) -> ModelWork:
    return ModelWork.from_config(c)
