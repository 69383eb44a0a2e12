"""Pallas TPU grouped matmul for MoE expert compute: (E, C, D) x (E, D, F) -> (E, C, F).

Tiling: grid = (E, C/bc, F/bf, D/bd); a (bc, bf) fp32 accumulator lives in VMEM
scratch across the (sequential, innermost) D dimension. ``group_sizes`` carries the
*ragged* occupancy of each expert's capacity buffer: row blocks entirely beyond an
expert's live rows are skipped structurally — the kernel does no MXU work for
padding, which is where the load-balancing win (immune router -> even group sizes ->
no straggler expert tile) becomes wall-clock time on TPU.

Block shapes default to MXU-aligned (128, 128) tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(sizes_ref, x_ref, w_ref, o_ref, acc_scr, *, bc: int, bd: int, nd: int):
    i = pl.program_id(1)          # row (capacity) block
    kd = pl.program_id(3)         # contraction block (sequential innermost)

    @pl.when(kd == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    live = sizes_ref[0] > i * bc  # ragged skip: no live rows in this block

    @pl.when(live)
    def _compute():
        x = x_ref[0].astype(jnp.float32)                    # (bc, bd)
        w = w_ref[0].astype(jnp.float32)                    # (bd, bf)
        acc_scr[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kd == nd - 1)
    def _finalize():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bc", "bf", "bd", "interpret"))
def moe_gmm(x, w, group_sizes, *, bc: int = 128, bf: int = 128, bd: int = 128,
            interpret: bool = False):
    """x: (E, C, D); w: (E, D, F); group_sizes: (E,) int32 live rows per expert."""
    e, c, d = x.shape
    f = w.shape[-1]
    bc, bf, bd = min(bc, c), min(bf, f), min(bd, d)
    assert c % bc == 0 and f % bf == 0 and d % bd == 0, (x.shape, w.shape)
    nc, nf, nd = c // bc, f // bf, d // bd

    kernel = functools.partial(_kernel, bc=bc, bd=bd, nd=nd)
    return pl.pallas_call(
        kernel,
        grid=(e, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1,), lambda e_, i, j, kd: (e_,),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bc, bd), lambda e_, i, j, kd: (e_, i, kd)),
            pl.BlockSpec((1, bd, bf), lambda e_, i, j, kd: (e_, kd, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e_, i, j, kd: (e_, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=interpret,
    )(group_sizes, x, w)
