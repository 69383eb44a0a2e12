"""Decoder stack orchestration: blocks, scan-over-layers, hybrid patterns, KV caches.

Layers are stacked (leading dim = depth) and applied with ``lax.scan`` — this keeps
the HLO size O(1) in depth (compile time and program size matter at 61-layer/1T scale)
and is the unit remat wraps around. Hybrid archs (recurrentgemma) tile their
``block_pattern`` as scan-over-groups plus an unrolled tail.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers, moe, rglru, ssm
from .layers import init_rmsnorm, rmsnorm

Array = jax.Array

MIXER_KINDS = ("attn", "local", "moe", "ssm", "rglru")


def layer_kinds(cfg: ModelConfig) -> list[str]:
    if cfg.family == "moe":
        return ["moe"] * cfg.num_layers
    if cfg.family == "ssm":
        return ["ssm"] * cfg.num_layers
    if cfg.family == "hybrid":
        pat = list(cfg.block_pattern)
        return [pat[i % len(pat)] for i in range(cfg.num_layers)]
    return ["attn"] * cfg.num_layers


def segments(cfg: ModelConfig) -> list[tuple[tuple[str, ...], int]]:
    """Decompose the depth into (pattern, repeats) scan segments."""
    kinds = layer_kinds(cfg)
    if cfg.family == "hybrid":
        pat = tuple(cfg.block_pattern)
        reps = cfg.num_layers // len(pat)
        segs = [(pat, reps)] if reps else []
        tail = tuple(kinds[reps * len(pat):])
        if tail:
            segs.append((tail, 1))
        return segs
    return [((kinds[0],), cfg.num_layers)]


# ---------------------------------------------------------------------------
# one block
# ---------------------------------------------------------------------------
def init_block(key, cfg: ModelConfig, kind: str) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model)}
    if kind in ("attn", "local"):
        p["mixer"] = layers.init_attention(k1, cfg)
        p["norm2"] = init_rmsnorm(cfg.d_model)
        p["mlp"] = layers.init_mlp(k2, cfg)
    elif kind == "moe":
        p["mixer"] = layers.init_attention(k1, cfg)
        p["norm2"] = init_rmsnorm(cfg.d_model)
        p["moe"] = moe.init_moe(k2, cfg)
    elif kind == "ssm":
        p["mixer"] = ssm.init_ssm(k1, cfg)
    elif kind == "rglru":
        p["mixer"] = rglru.init_rglru(k1, cfg)
        p["norm2"] = init_rmsnorm(cfg.d_model)
        p["mlp"] = layers.init_mlp(k2, cfg)
    else:
        raise ValueError(kind)
    return p


def apply_block(p, x: Array, cfg: ModelConfig, kind: str,
                bias: Optional[Array] = None, prefix_len: Optional[Array] = None):
    """Full-sequence block. Returns (x, moe_stats | None)."""
    stats = None
    x = layers.constrain(x, layers.DP, None, None)
    h = rmsnorm(p["norm1"], x, cfg)
    if kind in ("attn", "moe"):
        x = x + layers.attention(p["mixer"], h, cfg, prefix_len=prefix_len)
    elif kind == "local":
        x = x + layers.attention(p["mixer"], h, cfg, window=cfg.local_window,
                                 prefix_len=prefix_len)
    elif kind == "ssm":
        return x + ssm.ssm_block(p["mixer"], h, cfg), None
    elif kind == "rglru":
        x = x + rglru.rglru_block(p["mixer"], h, cfg)
    if kind == "moe":
        y, stats = moe.moe_ffn(p["moe"], rmsnorm(p["norm2"], x, cfg), cfg, bias)
        x = x + y
    else:
        x = x + layers.mlp(p["mlp"], rmsnorm(p["norm2"], x, cfg), cfg)
    return x, stats


def apply_block_decode(p, x: Array, cfg: ModelConfig, kind: str, cache, pos,
                       bias: Optional[Array] = None,
                       table: Optional[Array] = None,
                       active: Optional[Array] = None,
                       attn_backend: str = "xla"):
    """One-token block step. Returns (x, new_cache, moe_stats | None).
    ``table``/``active`` switch full-attention layers onto the paged KV path
    (serving engine): ``cache`` is then the layer's page pool, read only, and
    ``new_cache`` the token's K/V, which the stack writes after its layer
    scan. Sliding-window and recurrent layers keep their slot-row caches
    either way. ``attn_backend`` selects the paged attention compute (XLA
    gather oracle vs the Pallas block-table kernel)."""
    stats = None
    h = rmsnorm(p["norm1"], x, cfg)
    if kind in ("attn", "moe"):
        if table is not None:
            y, cache = layers.attention_decode_paged(p["mixer"], h, cfg, cache,
                                                     pos, table, active,
                                                     backend=attn_backend)
        else:
            y, cache = layers.attention_decode(p["mixer"], h, cfg, cache, pos)
        x = x + y
    elif kind == "local":
        y, cache = layers.attention_decode(p["mixer"], h, cfg, cache, pos,
                                           window=cfg.local_window)
        x = x + y
    elif kind == "ssm":
        y, cache = ssm.ssm_block_decode(p["mixer"], h, cfg, cache)
        return x + y, cache, None
    elif kind == "rglru":
        y, cache = rglru.rglru_block_decode(p["mixer"], h, cfg, cache)
        x = x + y
    if kind == "moe":
        y, stats = moe.moe_ffn(p["moe"], rmsnorm(p["norm2"], x, cfg), cfg, bias)
        x = x + y
    else:
        x = x + layers.mlp(p["mlp"], rmsnorm(p["norm2"], x, cfg), cfg)
    return x, cache, stats


def apply_block_verify(p, x: Array, cfg: ModelConfig, kind: str, cache,
                       pos, bias: Optional[Array] = None,
                       table: Optional[Array] = None,
                       active: Optional[Array] = None,
                       attn_backend: str = "xla"):
    """Sq-position verify block step (self-speculative decoding) against the
    paged pool. Attention-stack kinds only: attn/moe carry no slot-row state,
    so every (lane, position) row is independent — per row this is bitwise the
    ``apply_block_decode`` computation at that position. Recurrent kinds have
    cross-position state and are not verifiable in one batched step; the
    engine gates speculative decoding to attention stacks. Returns the rows'
    K/V in place of a new cache, for the stack to write."""
    if kind not in ("attn", "moe"):
        raise NotImplementedError(f"verify step unsupported for {kind!r}")
    stats = None
    h = rmsnorm(p["norm1"], x, cfg)
    y, cache = layers.attention_verify_paged(p["mixer"], h, cfg, cache, pos,
                                             table, active,
                                             backend=attn_backend)
    x = x + y
    if kind == "moe":
        y, stats = moe.moe_ffn(p["moe"], rmsnorm(p["norm2"], x, cfg), cfg, bias)
        x = x + y
    else:
        x = x + layers.mlp(p["mlp"], rmsnorm(p["norm2"], x, cfg), cfg)
    return x, cache, stats


def apply_block_prefill(p, x: Array, cfg: ModelConfig, kind: str, cache,
                        bias: Optional[Array] = None,
                        prefix_len: Optional[Array] = None):
    """Full-sequence block that also fills the decode cache."""
    stats = None
    h = rmsnorm(p["norm1"], x, cfg)
    if kind in ("attn", "moe"):
        y, cache = layers.attention_prefill(p["mixer"], h, cfg, cache,
                                            prefix_len=prefix_len)
        x = x + y
    elif kind == "local":
        y, cache = layers.attention_prefill(p["mixer"], h, cfg, cache,
                                            window=cfg.local_window,
                                            prefix_len=prefix_len)
        x = x + y
    elif kind == "ssm":
        y, cache = ssm.ssm_block_prefill(p["mixer"], h, cfg, cache)
        return x + y, cache, None
    elif kind == "rglru":
        y, cache = rglru.rglru_block_prefill(p["mixer"], h, cfg, cache)
        x = x + y
    if kind == "moe":
        y, stats = moe.moe_ffn(p["moe"], rmsnorm(p["norm2"], x, cfg), cfg, bias)
        x = x + y
    else:
        x = x + layers.mlp(p["mlp"], rmsnorm(p["norm2"], x, cfg), cfg)
    return x, cache, stats


def apply_block_prefill_chunk(p, x: Array, cfg: ModelConfig, kind: str, cache,
                              table_row: Array, p0: Array,
                              bias: Optional[Array] = None):
    """One prefill-chunk block step against the paged pool (full attention:
    returns the chunk's K/V for the stack to write) or the slot's recurrent
    state row (SSM). Sliding-window and RG-LRU layers are not chunkable
    (ring-slot remapping / associative-scan splits change the numerics) — the
    engine routes those configs to one-shot prefill."""
    stats = None
    h = rmsnorm(p["norm1"], x, cfg)
    if kind in ("attn", "moe"):
        y, cache = layers.attention_prefill_paged(p["mixer"], h, cfg, cache,
                                                  table_row, p0)
        x = x + y
    elif kind == "ssm":
        y, cache = ssm.ssm_block_prefill_chunk(p["mixer"], h, cfg, cache)
        return x + y, cache, None
    else:
        raise NotImplementedError(f"chunked prefill unsupported for {kind!r}")
    if kind == "moe":
        y, stats = moe.moe_ffn(p["moe"], rmsnorm(p["norm2"], x, cfg), cfg, bias)
        x = x + y
    else:
        x = x + layers.mlp(p["mlp"], rmsnorm(p["norm2"], x, cfg), cfg)
    return x, cache, stats


def apply_block_prefill_chunk_multi(p, x: Array, cfg: ModelConfig, kind: str,
                                    cache, tables: Array, p0s: Array,
                                    bias: Optional[Array] = None):
    """J concurrent prefill-chunk block steps against the paged pool in one
    call — attention-stack kinds only (attn/moe carry no slot-row cache, so
    lanes are fully independent; recurrent kinds stay on the one-job path).
    Returns the lanes' K/V for the stack to write."""
    if kind not in ("attn", "moe"):
        raise NotImplementedError(f"batched chunk prefill unsupported for "
                                  f"{kind!r}")
    stats = None
    h = rmsnorm(p["norm1"], x, cfg)
    y, cache = layers.attention_prefill_paged_multi(p["mixer"], h, cfg, cache,
                                                    tables, p0s)
    x = x + y
    if kind == "moe":
        y, stats = moe.moe_ffn(p["moe"], rmsnorm(p["norm2"], x, cfg), cfg, bias)
        x = x + y
    else:
        x = x + layers.mlp(p["mlp"], rmsnorm(p["norm2"], x, cfg), cfg)
    return x, cache, stats


def init_block_cache(cfg: ModelConfig, kind: str, batch: int, s_max: int, dtype):
    if kind in ("attn", "moe"):
        return layers.init_attention_cache(cfg, batch, s_max, dtype)
    if kind == "local":
        return layers.init_attention_cache(cfg, batch, min(s_max, cfg.local_window),
                                           dtype)
    if kind == "ssm":
        return ssm.init_ssm_cache(cfg, batch, dtype)
    if kind == "rglru":
        return rglru.init_rglru_cache(cfg, batch, dtype)
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------
def _maybe_remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


def init_stack(key, cfg: ModelConfig) -> list:
    """Returns a list (one per segment) of per-position stacked param trees."""
    segs = segments(cfg)
    out = []
    for si, (pattern, reps) in enumerate(segs):
        seg_params = []
        for pi, kind in enumerate(pattern):
            keys = jax.random.split(jax.random.fold_in(key, si * 97 + pi), reps)
            seg_params.append(jax.vmap(lambda k, kd=kind: init_block(k, cfg, kd))(keys))
        out.append(seg_params)
    return out


def apply_stack(stack_params: list, x: Array, cfg: ModelConfig,
                bias: Optional[Array] = None, prefix_len: Optional[Array] = None):
    """Full-sequence pass. ``bias``: (num_layers, E) immune router bias for MoE.
    Returns (x, stats (num_layers, E) load fractions | None, aux_loss, drop_frac)."""
    li = 0
    loads, auxs, drops = [], [], []
    for (pattern, reps), seg_params in zip(segments(cfg), stack_params):
        npos = len(pattern)
        seg_bias = None
        if bias is not None:
            seg_bias = bias[li:li + reps * npos].reshape(reps, npos, -1)
        li += reps * npos

        def body(carry, inp, pattern=pattern, npos=npos):
            xc = carry
            lp, b = inp
            sts = []
            for pi, kind in enumerate(pattern):
                bi = None if b is None else b[pi]
                xc, st = apply_block(lp[pi], xc, cfg, kind, bias=bi,
                                     prefix_len=prefix_len)
                if st is not None:
                    sts.append(st)
            out_st = jax.tree.map(lambda *a: jnp.stack(a), *sts) if sts else 0
            return xc, out_st

        body = _maybe_remat(body, cfg)
        xs = (seg_params, seg_bias)
        x, seg_stats = jax.lax.scan(body, x, xs)
        if isinstance(seg_stats, moe.MoEStats):
            loads.append(seg_stats.load_frac.reshape(-1, cfg.num_experts))
            auxs.append(seg_stats.aux_loss.reshape(-1))
            drops.append(seg_stats.drop_frac.reshape(-1))
    if loads:
        return (x, jnp.concatenate(loads), jnp.mean(jnp.concatenate(auxs)),
                jnp.mean(jnp.concatenate(drops)))
    return x, None, jnp.zeros(()), jnp.zeros(())


def init_stack_cache(cfg: ModelConfig, batch: int, s_max: int, dtype) -> list:
    """Stacked decode caches, mirroring the segment structure."""
    out = []
    for pattern, reps in segments(cfg):
        seg = []
        for kind in pattern:
            one = init_block_cache(cfg, kind, batch, s_max, dtype)
            seg.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a, (reps,) + a.shape).copy(), one))
        out.append(seg)
    return out


def map_block_caches(cfg: ModelConfig, fn, *trees):
    """Apply ``fn(kind, *per-layer-cache-dicts)`` across the stacked segment
    structure of one or more stack-cache trees, preserving the structure. The
    kind-aware analogue of ``jax.tree.map`` — paged full-attention leaves and
    slot-row recurrent leaves need different surgery and can't be told apart by
    leaf shape alone."""
    out = []
    for si, (pattern, reps) in enumerate(segments(cfg)):
        seg = []
        for pi, kind in enumerate(pattern):
            seg.append(fn(kind, *(t[si][pi] for t in trees)))
        out.append(seg)
    return out


def init_stack_cache_paged(cfg: ModelConfig, num_slots: int, s_max: int,
                           num_pages: int, page_size: int, dtype) -> list:
    """Paged decode caches: full-attention layers get a physical page pool
    (shared free list across slots, per-layer storage under one block table);
    sliding-window layers keep bounded slot-row ring buffers and recurrent
    layers their O(1) slot-row states — none of those holds a worst-case
    sequence reservation, so only full attention needs paging."""
    out = []
    for pattern, reps in segments(cfg):
        seg = []
        for kind in pattern:
            if kind in ("attn", "moe"):
                one = layers.init_attention_cache_paged(cfg, num_pages,
                                                        page_size, dtype)
            else:
                one = init_block_cache(cfg, kind, num_slots, s_max, dtype)
            seg.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a, (reps,) + a.shape).copy(), one))
        out.append(seg)
    return out


def _scan_paged(pattern, seg_cache: list) -> list:
    """What a layer scan takes of a segment's caches: a page pool stays whole
    (closed over; the scan takes its layer index and each layer gathers from
    the stack by (layer, page) — ``layers._gather_pages``), a slot-row cache
    is sliced per layer."""
    return [jnp.arange(cs["k"].shape[0]) if kind in ("attn", "moe") else cs
            for kind, cs in zip(pattern, seg_cache)]


def _layer_cache(kind: str, whole, scanned):
    """One layer's cache inside a scan over ``_scan_paged`` inputs."""
    if kind in ("attn", "moe"):
        return {"k": whole["k"], "v": whole["v"], "layer": scanned}
    return scanned


def _write_paged(pattern, seg_cache: list, scanned: list, table: Array,
                 p0: Array, active: Optional[Array] = None) -> list:
    """A segment's caches after a layer scan that read its page pools: each
    full-attention position's scan output is its layers' new K/V, written
    into the pool here, once (``layers.write_pages``); every other position's
    scan output is its new slot-row cache."""
    return [{n: layers.write_pages(cs[n], out[n], table, p0, active)
             for n in ("k", "v")} if kind in ("attn", "moe") else out
            for kind, cs, out in zip(pattern, seg_cache, scanned)]


def apply_stack_prefill(stack_params: list, x: Array, cfg: ModelConfig, caches: list,
                        bias: Optional[Array] = None,
                        prefix_len: Optional[Array] = None):
    """Full-sequence pass that fills the decode caches. Returns (x, new_caches)."""
    li = 0
    new_caches = []
    for (pattern, reps), seg_params, seg_cache in zip(segments(cfg), stack_params,
                                                      caches):
        npos = len(pattern)
        seg_bias = None
        if bias is not None:
            seg_bias = bias[li:li + reps * npos].reshape(reps, npos, -1)
        li += reps * npos

        def body(carry, inp, pattern=pattern):
            xc = carry
            lp, cs, b = inp
            new_cs = []
            for pi, kind in enumerate(pattern):
                bi = None if b is None else b[pi]
                xc, c2, _ = apply_block_prefill(lp[pi], xc, cfg, kind, cs[pi],
                                                bias=bi, prefix_len=prefix_len)
                new_cs.append(c2)
            return xc, new_cs

        x, nc = jax.lax.scan(body, x, (seg_params, seg_cache, seg_bias))
        new_caches.append(nc)
    return x, new_caches


def apply_stack_decode(stack_params: list, x: Array, cfg: ModelConfig, caches: list,
                       pos: Array, bias: Optional[Array] = None,
                       table: Optional[Array] = None,
                       active: Optional[Array] = None,
                       attn_backend: str = "xla"):
    """One-token pass. Returns (x, new_caches). ``table``/``active`` select the
    paged KV path for full-attention layers (closed over, same for every layer):
    the scan reads the page pools and emits each layer's new K/V, written into
    the pools once after it, so no scan carries a pool. ``attn_backend``
    picks its compute (XLA gather vs Pallas kernel). The bias
    rows scanned follow the *params'* repetition depth, not the config's, so a
    ``truncate_stack`` draft slice takes the leading layers' bias rows."""
    li = 0
    new_caches = []
    for (pattern, reps), seg_params, seg_cache in zip(segments(cfg), stack_params,
                                                      caches):
        npos = len(pattern)
        seg_bias = None
        if bias is not None:
            reps_p = jax.tree_util.tree_leaves(seg_params)[0].shape[0]
            seg_bias = bias[li:li + reps_p * npos].reshape(reps_p, npos, -1)
        li += reps * npos

        paged = table is not None

        def body(carry, inp, pattern=pattern, seg_cache=seg_cache):
            xc = carry
            lp, cs, b = inp
            new_cs = []
            for pi, kind in enumerate(pattern):
                bi = None if b is None else b[pi]
                c = _layer_cache(kind, seg_cache[pi], cs[pi]) if paged \
                    else cs[pi]
                xc, c2, _ = apply_block_decode(lp[pi], xc, cfg, kind, c, pos,
                                               bias=bi, table=table,
                                               active=active,
                                               attn_backend=attn_backend)
                new_cs.append(c2)
            return xc, new_cs

        seg_in = _scan_paged(pattern, seg_cache) if paged else seg_cache
        x, nc = jax.lax.scan(body, x, (seg_params, seg_in, seg_bias))
        if paged:
            nc = _write_paged(pattern, seg_cache, nc, table, pos, active)
        new_caches.append(nc)
    return x, new_caches


def apply_stack_verify(stack_params: list, x: Array, cfg: ModelConfig,
                       caches: list, pos: Array,
                       bias: Optional[Array] = None,
                       table: Optional[Array] = None,
                       active: Optional[Array] = None,
                       attn_backend: str = "xla"):
    """Sq-position verify pass (self-speculative decoding): every lane scores
    ``Sq`` consecutive positions starting at its ``pos`` in one batched step.
    Attention stacks only. Returns (x, new_caches)."""
    li = 0
    new_caches = []
    for (pattern, reps), seg_params, seg_cache in zip(segments(cfg), stack_params,
                                                      caches):
        npos = len(pattern)
        seg_bias = None
        if bias is not None:
            seg_bias = bias[li:li + reps * npos].reshape(reps, npos, -1)
        li += reps * npos

        def body(carry, inp, pattern=pattern, seg_cache=seg_cache):
            xc = carry
            lp, cs, b = inp
            new_cs = []
            for pi, kind in enumerate(pattern):
                bi = None if b is None else b[pi]
                xc, c2, _ = apply_block_verify(
                    lp[pi], xc, cfg, kind,
                    _layer_cache(kind, seg_cache[pi], cs[pi]), pos, bias=bi,
                    table=table, active=active, attn_backend=attn_backend)
                new_cs.append(c2)
            return xc, new_cs

        x, nc = jax.lax.scan(body, x, (seg_params,
                                       _scan_paged(pattern, seg_cache),
                                       seg_bias))
        new_caches.append(_write_paged(pattern, seg_cache, nc, table, pos,
                                       active))
    return x, new_caches


def truncate_stack(stack: list, depth: int) -> list:
    """First-``depth``-layer slice of a stacked param/cache tree (single-
    segment attention stacks — the self-speculative draft's weight reuse).
    The slice shares the leading-axis layout, so ``apply_stack_decode`` runs
    it unchanged: ``lax.scan`` infers the shorter depth from the sliced
    leading axis. Layer d's input depends only on layers < d, so the sliced
    pool's K/V *is* the truncated-depth model's cache — no separate draft
    weights or draft cache exist."""
    return [[jax.tree.map(lambda a: a[:depth], pos_params)
             for pos_params in seg] for seg in stack]


def apply_stack_prefill_chunk(stack_params: list, x: Array, cfg: ModelConfig,
                              caches: list, table_row: Array, p0: Array,
                              slot: Array, bias: Optional[Array] = None):
    """One prefill-chunk pass (batch-of-1) against the paged pool. Full-attention
    layers write the chunk's K/V into the slot's pages; recurrent (SSM) layers
    thread the slot's state row across chunks. Returns (x, new_caches)."""
    li = 0
    new_caches = []
    for (pattern, reps), seg_params, seg_cache in zip(segments(cfg), stack_params,
                                                      caches):
        npos = len(pattern)
        seg_bias = None
        if bias is not None:
            seg_bias = bias[li:li + reps * npos].reshape(reps, npos, -1)
        li += reps * npos
        # recurrent leaves are slot-indexed (reps, S, ...): slice the slot's row
        # outside the scan, write it back after
        seg_in = [jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(
                      a, slot, 1, axis=1), cs) if kind not in ("attn", "moe")
                  else cs for kind, cs in zip(pattern,
                                              _scan_paged(pattern, seg_cache))]

        def body(carry, inp, pattern=pattern, seg_cache=seg_cache):
            xc = carry
            lp, cs, b = inp
            new_cs = []
            for pi, kind in enumerate(pattern):
                bi = None if b is None else b[pi]
                xc, c2, _ = apply_block_prefill_chunk(
                    lp[pi], xc, cfg, kind,
                    _layer_cache(kind, seg_cache[pi], cs[pi]), table_row, p0,
                    bias=bi)
                new_cs.append(c2)
            return xc, new_cs

        x, nc = jax.lax.scan(body, x, (seg_params, seg_in, seg_bias))
        nc = [jax.tree.map(lambda full, row: jax.lax.dynamic_update_slice_in_dim(
                  full, row.astype(full.dtype), slot, axis=1), cs, c2)
              if kind not in ("attn", "moe") else c2
              for kind, cs, c2 in zip(pattern, seg_cache, nc)]
        new_caches.append(_write_paged(pattern, seg_cache, nc, table_row[None],
                                       jnp.reshape(p0, (1,))))
    return x, new_caches


def apply_stack_prefill_chunk_multi(stack_params: list, x: Array,
                                    cfg: ModelConfig, caches: list,
                                    tables: Array, p0s: Array,
                                    bias: Optional[Array] = None):
    """J concurrent prefill chunks (one lane per in-flight job) in a single
    pass against the paged pool — attn/moe stacks only, so there is no
    slot-row state to slice and every lane is independent. Padding lanes carry
    an all-null block table (writes land on the trash page, outputs are
    discarded by the host). Returns (x, new_caches)."""
    li = 0
    new_caches = []
    for (pattern, reps), seg_params, seg_cache in zip(segments(cfg), stack_params,
                                                      caches):
        npos = len(pattern)
        seg_bias = None
        if bias is not None:
            seg_bias = bias[li:li + reps * npos].reshape(reps, npos, -1)
        li += reps * npos

        def body(carry, inp, pattern=pattern, seg_cache=seg_cache):
            xc = carry
            lp, cs, b = inp
            new_cs = []
            for pi, kind in enumerate(pattern):
                bi = None if b is None else b[pi]
                xc, c2, _ = apply_block_prefill_chunk_multi(
                    lp[pi], xc, cfg, kind,
                    _layer_cache(kind, seg_cache[pi], cs[pi]), tables, p0s,
                    bias=bi)
                new_cs.append(c2)
            return xc, new_cs

        x, nc = jax.lax.scan(body, x, (seg_params,
                                       _scan_paged(pattern, seg_cache),
                                       seg_bias))
        new_caches.append(_write_paged(pattern, seg_cache, nc, tables, p0s))
    return x, new_caches
