"""Pallas TPU paged-decode attention: block-table K/V gather inside the kernel.

One query token per sequence (the serving engine's decode tick) attends over a
KV cache scattered across fixed-size physical pages. The block table and the
per-sequence lengths ride in as *scalar prefetch* (``PrefetchScalarGridSpec``),
so the BlockSpec index maps pick each logical page's physical page id before
the kernel body runs — the gather is the DMA schedule itself; no
(B, maxp*page, ...) contiguous K/V tensor ever exists in HBM.

Tiling: grid = (B, Hkv, maxp) with the logical-page dimension innermost and
sequential; the (m, l, acc) online-softmax state lives in VMEM scratch and
persists across page steps, exactly like the flash kernel's KV loop. The whole
GQA group of a kv head is one q block, so each grid step is a
(G, d) x (d, page) MXU tile. Pages entirely beyond a sequence's length are
skipped structurally (``pl.when``) — ragged page counts cost no compute, and
null-page (unmapped) table entries are never read live.

Layouts: q (B, Hkv, G, D); k_pages, v_pages (P, page, Hkv, D);
table (B, maxp) int32 physical page ids; lengths (B,) int32 valid positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(table_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, page: int, maxp: int):
    b = pl.program_id(0)
    j = pl.program_id(2)          # logical page (sequential innermost)
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # structural skip: the whole page is beyond this sequence's length
    @pl.when(j * page < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (G, d)
        k = k_ref[0, :, 0].astype(jnp.float32)               # (page, d)
        v = v_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / (q.shape[-1] ** 0.5))                 # (G, page)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] \
            + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == maxp - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _verify_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                   l_scr, acc_scr, *, page: int, maxp: int, sq: int, g: int):
    """k-position verify step: ``sq`` query rows per sequence, row ``r``
    (query position ``pos + r // g`` for GQA group lane ``r % g``) attends the
    causal prefix ``kpos <= pos + r // g``. Same online-softmax page loop as
    the 1-query decode kernel — the rows just carry a per-row causal bound
    instead of one shared length."""
    b = pl.program_id(0)
    j = pl.program_id(2)          # logical page (sequential innermost)
    pos = pos_ref[b]              # first query row's cache position

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # structural skip: the page is beyond even the deepest query row's bound
    @pl.when(j * page <= pos + (sq - 1))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # (sq*g, d)
        k = k_ref[0, :, 0].astype(jnp.float32)               # (page, d)
        v = v_ref[0, :, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / (q.shape[-1] ** 0.5))                 # (sq*g, page)
        kpos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qrow = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // g
        s = jnp.where(kpos <= pos + qrow, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] \
            + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == maxp - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sq", "interpret"))
def paged_attention_verify(q, k_pages, v_pages, table, pos, *, sq: int,
                           interpret: bool = False):
    """q: (B, Hkv, Sq*G, D) — ``sq`` query rows per kv head, (query, group)
    row-major; k_pages, v_pages: (P, page, Hkv, D); table: (B, maxp) int32;
    pos: (B,) int32 first query row's cache position -> (B, Hkv, Sq*G, D).
    Row ``r`` attends causally up to position ``pos + r // G`` — the batched
    verify step of self-speculative decoding (sq == 1 is exactly the decode
    kernel's contract with lengths = pos + 1)."""
    b, hk, sqg, d = q.shape
    page = k_pages.shape[1]
    maxp = table.shape[1]
    g = sqg // sq

    kernel = functools.partial(_verify_kernel, page=page, maxp=maxp, sq=sq,
                               g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hk, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, sqg, d),
                         lambda b_, h_, j, tbl, ps: (b_, h_, 0, 0)),
            pl.BlockSpec((1, page, 1, d),
                         lambda b_, h_, j, tbl, ps: (tbl[b_, j], 0, h_, 0)),
            pl.BlockSpec((1, page, 1, d),
                         lambda b_, h_, j, tbl, ps: (tbl[b_, j], 0, h_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, sqg, d),
                               lambda b_, h_, j, tbl, ps: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((sqg,), jnp.float32),     # running max m
            pltpu.VMEM((sqg,), jnp.float32),     # running sum l
            pltpu.VMEM((sqg, d), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, sqg, d), q.dtype),
        interpret=interpret,
    )(table, pos, q, k_pages, v_pages)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, table, lengths, *,
                    interpret: bool = False):
    """q: (B, Hkv, G, D); k_pages, v_pages: (P, page, Hkv, D);
    table: (B, maxp) int32; lengths: (B,) int32 -> (B, Hkv, G, D)."""
    b, hk, g, d = q.shape
    page = k_pages.shape[1]
    maxp = table.shape[1]

    kernel = functools.partial(_kernel, page=page, maxp=maxp)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hk, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, j, tbl, lens: (b_, h_, 0, 0)),
            pl.BlockSpec((1, page, 1, d),
                         lambda b_, h_, j, tbl, lens: (tbl[b_, j], 0, h_, 0)),
            pl.BlockSpec((1, page, 1, d),
                         lambda b_, h_, j, tbl, lens: (tbl[b_, j], 0, h_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda b_, h_, j, tbl, lens: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),       # running max m
            pltpu.VMEM((g,), jnp.float32),       # running sum l
            pltpu.VMEM((g, d), jnp.float32),     # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hk, g, d), q.dtype),
        interpret=interpret,
    )(table, lengths, q, k_pages, v_pages)
