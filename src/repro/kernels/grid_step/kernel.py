"""Pallas TPU stencil for the blackboard max-diffusion step (the paper's hot op).

One synchronous step of ``label := max(label, 4-neighbour labels)`` within a
conductor mask — the propagation/fixpoint operation the VLSI extractor's observer
(and a batched variant of the propagator agents) applies per cycle.

Tiling: grid over row bands; each step reads its (band, W) block plus the
neighbouring bands through *three* BlockSpecs onto the same array with shifted
(clamped) index maps — the Pallas TPU idiom for halo exchange without overlapping
block support. Edge duplication from clamping is masked off with program-id
predicates. W stays whole per block (layout: rows are the tiled dim, the lane dim
stays dense/128-aligned for the VPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(lab_prev_ref, lab_cur_ref, lab_next_ref,
            cond_prev_ref, cond_cur_ref, cond_next_ref, o_ref, *, nb: int):
    i = pl.program_id(0)
    lab = lab_cur_ref[...]
    cond = cond_cur_ref[...] > 0
    band, w = lab.shape

    # in-band 4-neighbour shifts (zeros roll in at band edges, fixed up below)
    up = jnp.pad(lab[1:], ((0, 1), (0, 0)))
    down = jnp.pad(lab[:-1], ((1, 0), (0, 0)))
    left = jnp.pad(lab[:, 1:], ((0, 0), (0, 1)))
    right = jnp.pad(lab[:, :-1], ((0, 0), (1, 0)))
    cup = jnp.pad(cond_cur_ref[...][1:] > 0, ((0, 1), (0, 0)))
    cdown = jnp.pad(cond_cur_ref[...][:-1] > 0, ((1, 0), (0, 0)))
    cleft = jnp.pad(cond_cur_ref[...][:, 1:] > 0, ((0, 0), (0, 1)))
    cright = jnp.pad(cond_cur_ref[...][:, :-1] > 0, ((0, 0), (1, 0)))

    # halo rows from the neighbouring bands (masked at the outer edges, where the
    # clamped index maps would alias the current band)
    first_of_next = jnp.where(i < nb - 1, lab_next_ref[0], 0)
    cfirst_of_next = jnp.where(i < nb - 1, cond_next_ref[0] > 0, False)
    last_of_prev = jnp.where(i > 0, lab_prev_ref[band - 1], 0)
    clast_of_prev = jnp.where(i > 0, cond_prev_ref[band - 1] > 0, False)
    up = up.at[band - 1].set(first_of_next)
    cup = cup.at[band - 1].set(cfirst_of_next)
    down = down.at[0].set(last_of_prev)
    cdown = cdown.at[0].set(clast_of_prev)

    out = lab
    for nb_lab, nb_cond in ((up, cup), (down, cdown), (left, cleft),
                            (right, cright)):
        out = jnp.maximum(out, jnp.where(nb_cond & cond, nb_lab, 0))
    o_ref[...] = jnp.where(cond, out, lab)


@functools.partial(jax.jit, static_argnames=("band", "interpret"))
def grid_step(labels, cond, *, band: int = 8, interpret: bool = False):
    """labels, cond: (H, W) int32 -> (H, W) one masked max-diffusion step."""
    h, w = labels.shape
    band = min(band, h)
    while h % band:
        band -= 1
    nb = h // band

    kernel = functools.partial(_kernel, nb=nb)
    prev_map = lambda i: (jnp.maximum(i - 1, 0), 0)
    cur_map = lambda i: (i, 0)
    next_map = lambda i: (jnp.minimum(i + 1, nb - 1), 0)
    spec = lambda m: pl.BlockSpec((band, w), m)
    return pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[spec(prev_map), spec(cur_map), spec(next_map),
                  spec(prev_map), spec(cur_map), spec(next_map)],
        out_specs=spec(cur_map),
        out_shape=jax.ShapeDtypeStruct((h, w), labels.dtype),
        interpret=interpret,
    )(labels, labels, labels, cond, cond, cond)
