"""Pallas TPU flash attention: blocked causal GQA attention with online softmax.

Tiling (VMEM): grid = (B, H, S/bq, S/bkv) with the KV dimension innermost and
*sequential* — the (m, l, acc) running state lives in VMEM scratch and persists
across KV steps, exactly the TPU-native adaptation of the GPU flash algorithm
(the MXU consumes (bq, d) x (d, bkv) tiles; no (S, S) tensor ever exists in HBM).
Fully-masked causal blocks are skipped structurally (pl.when), so the causal
speedup is real compute skipped, not masked-and-wasted.

Layouts: q (B, H, S, D); k, v (B, Hkv, S, D); GQA maps q-head h -> kv-head
h // (H // Hkv) in the BlockSpec index maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
            *, bq: int, bkv: int, nkv: int, causal: bool, groups: int):
    del groups
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block (sequential innermost)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal structural skip: the whole kv block is in the future
    live = (j * bkv <= i * bq + (bq - 1)) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / (q.shape[-1] ** 0.5))
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            kpos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] \
            + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nkv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bkv", "causal", "interpret"))
def flash_attention(q, k, v, *, bq: int = 128, bkv: int = 128,
                    causal: bool = True, interpret: bool = False):
    """q: (B, H, S, D); k, v: (B, Hkv, S, D) -> (B, H, S, D)."""
    b, h, s, d = q.shape
    hk = k.shape[1]
    groups = h // hk
    bq = min(bq, s)
    bkv = min(bkv, s)
    assert s % bq == 0 and s % bkv == 0, (s, bq, bkv)
    nq, nkv = s // bq, s // bkv

    kernel = functools.partial(_kernel, bq=bq, bkv=bkv, nkv=nkv,
                               causal=causal, groups=groups)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h_, i, j, g=groups: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda b_, h_, i, j, g=groups: (b_, h_ // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max m
            pltpu.VMEM((bq,), jnp.float32),       # running sum l
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
