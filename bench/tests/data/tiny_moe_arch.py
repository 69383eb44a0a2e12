"""A mixture-of-experts decoder in the program's MoE family, at test size:
the llama layout (``archs/llama.py``) with each MLP replaced by routed
experts. The architecture module contract is at the top of
``archs/llama.py``; this module is named by ``tiny-moe.json``.

The MoE half of a block, as the program computes it (``models/moe.py``,
``core/router.py``): router logits ``h @ w_router``; the ``k`` experts of
the largest ``logits + bias`` (the bias steers selection only); gates the
softmax over the selected experts' unbiased logits; each selected expert's
SwiGLU; their gate-weighted sum. The router and its bias are kept in
float32, as the program keeps them; no token is dropped, since the
program's capacity factor is set to ``E / k``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

import weights
from harness import load_arch
from reference import F32, mm

llama = load_arch({"bench_arch": "bench/archs/llama.py"})


def program_config(m: dict):
    """Dropless: a capacity factor of ``E / k`` keeps chunked prefill and
    prefix sharing on (the engine's ``_chunkable``)."""
    e, k = m["num_local_experts"], m["num_experts_per_tok"]
    return dataclasses.replace(llama.program_config(m), num_experts=e,
                               experts_per_token=k, capacity_factor=e / k)


def shapes(c: dict) -> dict:
    L, d, f = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    e = c["num_local_experts"]
    s = {n: v for n, v in llama.shapes(c).items()
         if n not in ("w_gate", "w_up", "w_down")}
    s.update(w_router=(L, d, e), w_gate=(L, e, d, f), w_up=(L, e, d, f),
             w_down=(L, e, f, d))
    return s


def make_weights(c: dict, seed: int) -> dict:
    """The llama draw, with the experts in the configuration's type and the
    router and a selection bias ~ N(0, 0.5^2) in float32."""
    dt = jnp.dtype(c["torch_dtype"])
    mats = tuple((n, s, llama.std(n, s), F32 if n == "w_router" else dt)
                 for n, s in shapes(c).items())
    bias = ("router_bias", (c["num_hidden_layers"], c["num_local_experts"]),
            0.5, F32)
    return weights.draw(weights.key_of(seed), mats + (bias,),
                        llama.gain_shapes(c))


def block(w: dict) -> dict:
    b = llama.block(w)
    b["moe"] = dict(b.pop("mlp"), w_router=w["w_router"])
    return b


def program_params(w: dict) -> dict:
    return llama.program_params(w, blk=block)


def engine_kwargs(w: dict) -> dict:
    return {"router_bias": w["router_bias"]}


@partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim", "eps",
                                   "theta", "quant", "k"))
def _layer(x, lw, *, heads, kv_heads, head_dim, eps, theta, quant, k):
    x = llama.attend(x, lw, heads=heads, kv_heads=kv_heads,
                     head_dim=head_dim, eps=eps, theta=theta, quant=quant)
    h = llama.rms(x, lw["norm2"], eps)
    r = mm(h, lw["w_router"], quant)                            # (S, E)
    _, idx = jax.lax.top_k(r + lw["router_bias"], k)            # (S, k)
    gate = jax.nn.softmax(jnp.take_along_axis(r, idx, -1), -1)

    def expert(wg, wu, wd):
        return mm(jax.nn.silu(mm(h, wg, quant)) * mm(h, wu, quant), wd, quant)
    out = jax.vmap(expert, out_axes=1)(lw["w_gate"], lw["w_up"],
                                       lw["w_down"])           # (S, E, D)
    sel = jnp.take_along_axis(out, idx[:, :, None], 1)          # (S, k, D)
    return x + jnp.sum(sel * gate[:, :, None], 1)


def logits(w: dict, c: dict, tokens, quant=None):
    return llama.logits(
        w, c, tokens, quant, layer=partial(_layer, k=c["num_experts_per_tok"]),
        names=("wq", "wk", "wv", "wo", "norm1", "norm2", "w_router",
               "router_bias", "w_gate", "w_up", "w_down"))


@dataclass(frozen=True)
class MoEWork(llama.ModelWork):
    """Each token multiplies the attention, the router and its ``per_token``
    experts. A decode tick reads at least ``per_token`` experts of each
    layer; which ones, and how many more, the program does not count yet,
    so the bytes of a tick are its least."""
    experts: int = 0
    per_token: int = 0
    router_bytes: int = 4      # the router is kept in float32

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_ff

    @property
    def layer_matmul_params(self) -> int:
        return self.attn_params + self.d_model * self.experts \
            + self.per_token * self.expert_params

    @property
    def param_bytes(self) -> int:
        mats = self.layers * (self.attn_params
                              + self.experts * self.expert_params) \
            + self.head_params * (1 if self.tied else 2)
        router = self.layers * self.d_model * self.experts
        norms = (2 * self.layers + 1) * self.d_model
        return mats * self.weight_bytes + router * self.router_bytes \
            + norms * self.norm_bytes

    def decode_tick(self, step) -> tuple[int, int]:
        flops, nbytes = super().decode_tick(step)
        unread = self.layers * (self.experts - self.per_token) \
            * self.expert_params * self.weight_bytes
        return flops, nbytes - unread


def work(c: dict) -> MoEWork:
    return MoEWork(**dataclasses.asdict(llama.work(c)),
                   experts=c["num_local_experts"],
                   per_token=c["num_experts_per_tok"])
