"""Building-block layers (pure JAX, no framework): norms, rotary, attention, MLPs.

Conventions:
  * params are nested dicts of jnp arrays; init_* builds them, apply is a pure fn
  * activations follow ``cfg.dtype`` (bf16 on TPU); softmax/normalization in fp32
  * attention supports MHA / GQA / MQA (num_kv_heads), optional qk-norm, optional
    local (sliding-window) masking, and a KV-cache decode path
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig

Array = jax.Array


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# mesh-aware activation sharding constraints
# ---------------------------------------------------------------------------
DP = ("pod", "data")   # logical data-parallel axes (filtered to the live mesh)

# Axis names/sizes the current launcher's mesh provides. Classic `with mesh:`
# contexts do not populate jax.sharding.get_abstract_mesh(), so launchers (dryrun,
# train, serve) declare their mesh explicitly via set_mesh_axes(); CPU unit tests
# leave this empty and every constraint is a no-op.
_MESH_AXES: dict[str, int] = {}


def set_mesh_axes(axes, sizes=None) -> None:
    global _MESH_AXES
    if hasattr(axes, "shape") and hasattr(axes.shape, "keys"):  # a Mesh
        _MESH_AXES = dict(axes.shape)
    elif sizes is not None:
        _MESH_AXES = dict(zip(tuple(axes), tuple(sizes)))
    else:
        _MESH_AXES = {a: 0 for a in axes}      # sizes unknown: no divisibility check


def _current_axes() -> dict[str, int]:
    if _MESH_AXES:
        return _MESH_AXES
    try:
        m = jax.sharding.get_abstract_mesh()
        return dict(m.shape) if m is not None and m.axis_names else {}
    except Exception:
        return {}


def guard_entry(s, dim: int, axes: dict[str, int]):
    """One PartitionSpec entry of the shared axis-drop policy.

    This is THE guard — used by the activation constraints here and by
    ``dist.sharding``'s NamedSharding rules, so the two layout policies cannot
    drift. Axis names the mesh doesn't have are dropped; an entry whose
    surviving axes' total size doesn't divide the dim it would split is dropped
    whole (splitting anyway forces GSPMD into pad-and-rematerialize). Axis
    sizes recorded as 0 mean "unknown" and skip the divisibility check.
    Returns None, an axis name, or a tuple of axis names."""
    if s is None:
        return None
    is_seq = isinstance(s, (tuple, list))
    cand = tuple(a for a in (s if is_seq else (s,)) if a in axes)
    if not cand:
        return None
    size, known = 1, True
    for a in cand:
        if axes[a]:
            size *= axes[a]
        else:
            known = False
    if known and dim % size != 0:
        return None
    return cand if is_seq else cand[0]


def constrain(x: Array, *spec) -> Array:
    """with_sharding_constraint that no-ops outside a mesh, drops axis names the
    current mesh doesn't have, and drops axes that don't divide their dim (an
    8-kv-head tensor constrained over a 16-way axis forces GSPMD into involuntary
    full rematerialization — observed, not hypothetical)."""
    axes = _current_axes()
    if not axes:
        return x

    filtered = tuple(guard_entry(s, d, axes) for s, d in zip(spec, x.shape))
    if all(s is None for s in filtered):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*filtered))


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------
def dense_init(key, in_dim: int, out_dim: int, dtype) -> Array:
    scale = 1.0 / jnp.sqrt(in_dim)
    return (jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> Array:
    return (jax.random.normal(key, (vocab, dim), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def init_rmsnorm(dim: int) -> dict:
    return {"scale": jnp.zeros((dim,), jnp.float32)}


def rmsnorm(params, x: Array, cfg: ModelConfig) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(var + cfg.norm_eps)
    # zeros-init scale applied as (1 + g) — covers both the llama and gemma
    # conventions (they differ only in checkpoint layout, which we do not load)
    return (xf * (params["scale"] + 1.0)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------
def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # (..., S, 1, half): broadcast over the head dimension
    angles = positions[..., :, None, None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig) -> dict:
    dt = dtype_of(cfg)
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, hk, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = {
        "wq": dense_init(kq, d, h * hd, dt),
        "wk": dense_init(kk, d, hk * hd, dt),
        "wv": dense_init(kv, d, hk * hd, dt),
        "wo": dense_init(ko, h * hd, d, dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd)
        p["k_norm"] = init_rmsnorm(hd)
    return p


def _qkv(params, x: Array, cfg: ModelConfig, positions: Array):
    b, s, _ = x.shape
    h, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    # heads shard over 'model' (padded when h % tp != 0 — local waste, no gather);
    # kv heads follow q heads (GQA groups stay co-located)
    q = constrain((x @ params["wq"]).reshape(b, s, h, hd), DP, None, "model", None)
    k = constrain((x @ params["wk"]).reshape(b, s, hk, hd), DP, None, "model", None)
    v = constrain((x @ params["wv"]).reshape(b, s, hk, hd), DP, None, "model", None)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q, cfg)
        k = rmsnorm(params["k_norm"], k, cfg)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa(q: Array, k: Array, v: Array, mask: Array, cfg: ModelConfig) -> Array:
    """q: (B,Sq,H,D); k,v: (B,Skv,Hkv,D); mask: (B|1, Sq, Skv) bool (True=attend)."""
    b, sq, h, hd = q.shape
    hk = k.shape[2]
    g = h // hk
    q = q.reshape(b, sq, hk, g, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, sq, h * hd)


def _block_mask(q0, k0, cq: int, ckv: int, window: Optional[int],
                prefix_len: Optional[Array]) -> Array:
    """(cq, ckv) mask for a (q-chunk, kv-chunk) block at offsets (q0, k0)."""
    qpos = q0 + jnp.arange(cq)[:, None]
    kpos = k0 + jnp.arange(ckv)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    if prefix_len is not None:
        m |= (qpos < prefix_len) & (kpos < prefix_len)
    return m


def _sdpa_chunked(q: Array, k: Array, v: Array, cfg: ModelConfig,
                  window: Optional[int], prefix_len: Optional[Array],
                  q_chunk: int = 1024, kv_chunk: int = 1024) -> Array:
    """Flash-style attention on the XLA path: online softmax over KV chunks inside a
    scan over Q chunks, with the inner pass rematerialized in the backward pass.
    Never materializes the (S, S) score matrix — this is what keeps the 32k prefill
    dry-run inside HBM. (On real TPU the Pallas kernel replaces this; same contract.)
    """
    b, s, h, hd = q.shape
    hk = k.shape[2]
    g = h // hk

    def pick(target):
        # largest power-of-two chunk <= target that divides s (handles odd lengths
        # like 32768 + a 256-patch VLM prefix)
        c = min(target, s)
        while c > 1 and s % c:
            c //= 2
        return max(c, 1)

    cq, ckv = pick(q_chunk), pick(kv_chunk)
    nq, nkv = s // cq, s // ckv

    # (B, K, G, S, D) / (B, K, S, D)
    qt = q.reshape(b, s, hk, g, hd).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    def q_block(qi):
        qc = jax.lax.dynamic_slice_in_dim(qt, qi * cq, cq, axis=3)

        def kv_body(carry, kj):
            m_run, l_run, acc = carry
            kc = jax.lax.dynamic_slice_in_dim(kt, kj * ckv, ckv, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(vt, kj * ckv, ckv, axis=2)
            s_blk = jnp.einsum("bkgqd,bktd->bkgqt", qc, kc).astype(jnp.float32)
            s_blk = s_blk * scale
            mask = _block_mask(qi * cq, kj * ckv, cq, ckv, window, prefix_len)
            s_blk = jnp.where(mask, s_blk, -1e30)
            m_new = jnp.maximum(m_run, jnp.max(s_blk, axis=-1))
            p = jnp.exp(s_blk - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] \
                + jnp.einsum("bkgqt,bktd->bkgqd", p, vc.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hk, g, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hk, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hk, g, cq, hd), jnp.float32)
        (m_f, l_f, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0),
                                          jnp.arange(nkv, dtype=jnp.int32))
        return acc / jnp.maximum(l_f, 1e-30)[..., None]

    # scan over q chunks; each chunk's inner pass is rematerialized in bwd
    blocks = jax.lax.map(jax.checkpoint(q_block), jnp.arange(nq, dtype=jnp.int32))
    # blocks: (NQ, B, K, G, CQ, D) -> (B, S, H*D)
    out = blocks.transpose(1, 2, 3, 0, 4, 5).reshape(b, hk, g, s, hd)
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, s, h * hd)
    return out.astype(v.dtype)


_CHUNK_THRESHOLD = 2048  # use the chunked path for sequences beyond this


def _sdpa_dispatch(q, k, v, cfg: ModelConfig, window, prefix_len) -> Array:
    s = q.shape[1]
    if s > _CHUNK_THRESHOLD:
        return _sdpa_chunked(q, k, v, cfg, window, prefix_len)
    return _sdpa(q, k, v, causal_mask(s, s, window, prefix_len), cfg)


def causal_mask(sq: int, skv: int, window: Optional[int] = None,
                prefix_len: Optional[Array] = None) -> Array:
    """(1, sq, skv) causal (optionally sliding-window) mask; sq positions are the
    last sq of skv. ``prefix_len`` enables bidirectional attention within the first
    ``prefix_len`` positions (prefix-LM, e.g. PaliGemma's image prefix)."""
    qpos = jnp.arange(sq)[:, None] + (skv - sq)
    kpos = jnp.arange(skv)[None, :]
    m = kpos <= qpos
    if window is not None:
        m &= kpos > qpos - window
    if prefix_len is not None:
        m |= (qpos < prefix_len) & (kpos < prefix_len)
    return m[None]


def attention(params, x: Array, cfg: ModelConfig, window: Optional[int] = None,
              prefix_len: Optional[Array] = None) -> Array:
    """Full-sequence (train) attention."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _qkv(params, x, cfg, positions)
    out = _sdpa_dispatch(q, k, v, cfg, window, prefix_len)
    return out @ params["wo"]


def attention_prefill(params, x: Array, cfg: ModelConfig, cache: dict,
                      window: Optional[int] = None,
                      prefix_len: Optional[Array] = None):
    """Full-sequence pass that also fills the decode cache with k/v.

    For sliding-window layers the cache is a ring buffer of size window; we store
    the last ``window`` positions at their ring slots."""
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _qkv(params, x, cfg, positions)
    out = _sdpa_dispatch(q, k, v, cfg, window, prefix_len)
    s_max = cache["k"].shape[1]
    if window is not None and s > s_max:
        # keep only the last s_max positions, placed at their ring-buffer slots
        slots = (jnp.arange(s - s_max, s)) % s_max
        ck = cache["k"].at[:, slots].set(k[:, -s_max:])
        cv = cache["v"].at[:, slots].set(v[:, -s_max:])
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], k[:, :s_max], (0, 0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v[:, :s_max], (0, 0, 0, 0))
    return out @ params["wo"], {"k": ck, "v": cv}


def attention_decode(params, x: Array, cfg: ModelConfig, cache: dict, pos: Array,
                     window: Optional[int] = None):
    """One-token decode. cache: {'k','v': (B, S_max, Hkv, D)}; ``pos`` is the
    current index — () for a lockstep batch, or (B,) when each batch row sits at
    its own depth (the serving engine's continuous-batching slots).

    Returns (out, new_cache). The cache is a ring buffer when ``window`` is set
    (bounded memory for sliding-window layers)."""
    b = x.shape[0]
    pos = jnp.asarray(pos)
    per_slot = pos.ndim == 1
    positions = pos[:, None] if per_slot else jnp.broadcast_to(pos, (b, 1))
    q, k, v = _qkv(params, x, cfg, positions)
    s_max = cache["k"].shape[1]
    slot = pos % s_max if window is not None else pos
    if per_slot:
        rows = jnp.arange(b)
        ck = cache["k"].at[rows, slot].set(k[:, 0])
        cv = cache["v"].at[rows, slot].set(v[:, 0])
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
    kpos = jnp.arange(s_max)[None, :]
    qref = pos[:, None] if per_slot else pos          # (B, 1) rows or () scalar
    sref = slot[:, None] if per_slot else slot
    if window is not None:
        # ring buffer: valid slots are the last min(pos+1, s_max) written
        age = (sref - kpos) % s_max
        mask = age < jnp.minimum(qref + 1, s_max)
    else:
        mask = kpos <= qref
    out = _sdpa(q, ck, cv, mask[:, None, :], cfg)     # (B|1, 1, S_max) mask
    return out @ params["wo"], {"k": ck, "v": cv}


def init_attention_cache(cfg: ModelConfig, batch: int, s_max: int, dtype) -> dict:
    hk, hd = cfg.num_kv_heads, cfg.head_dim
    return {"k": jnp.zeros((batch, s_max, hk, hd), dtype),
            "v": jnp.zeros((batch, s_max, hk, hd), dtype)}


# ---------------------------------------------------------------------------
# paged attention (block-table KV cache — the serving engine's memory plane)
# ---------------------------------------------------------------------------
# A layer's pool stores, for each KV head, page p as ONE contiguous row of
# page*D values ((page, D) row-major): K/V as (Hkv, P, page*D). A
# (P, page, Hkv, D) pool would tile its two minor dims (Hkv, D) on the TPU's
# (8, 128) tiles — (5, 64) for smollm-360m pads 6x — so the compiler stores it
# page-index-minor, and every gather by page first re-lays the whole pool
# out. A row pads at most its one minor dim and is gathered as it lies; the
# KV head leads so that a gather by (head, page) lands each head's pages in
# sequence order, the (B, Hkv, S, D) the attention contraction reads, where
# a row of all heads would need a transpose of every gathered page.
#
# The attention functions below read a layer's pool and never write it: each
# returns the K/V of its own positions, which it lays over the gathered pages
# for its attention, and the stack writes every layer's new K/V once, after
# its layer scan (``write_pages``) — so no scan carries a pool. A layer sees
# its cache as ``{'k','v': (L, Hkv, P, page*D) the stack's pools, 'layer':
# index}`` and gathers by (layer, head, page) from the whole stack: slicing
# its layer out would copy it.
def init_attention_cache_paged(cfg: ModelConfig, num_pages: int, page_size: int,
                               dtype) -> dict:
    """Physical page pool for one attention layer: K/V as (Hkv, P, page*D),
    one row per KV head and page. Page 0 is the null/trash page (see
    ``serve.paging``)."""
    shape = (cfg.num_kv_heads, num_pages, page_size * cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def page_view(pages: Array, cfg: ModelConfig) -> Array:
    """(..., Hkv, P, page*D) rows -> the (..., P, page, Hkv, D) page view."""
    rows = pages.reshape(*pages.shape[:-1], -1, cfg.head_dim)
    return jnp.moveaxis(rows, -4, -2)


def _gather_pages(pages: Array, layer: Array, table: Array,
                  cfg: ModelConfig) -> Array:
    """pages: (L, Hkv, P, page*D); table: (B, maxp) -> layer ``layer``'s
    (B, maxp*page, Hkv, D).

    The gathered sequence is the slot's cache in logical order, padded by null
    pages to exactly maxp*page positions — when maxp*page == max_cache this is
    the same K/V tensor (values *and* shape) the dense slot-row layout holds,
    so the masked softmax downstream is bitwise identical to the unpaged path.
    """
    b, hk = table.shape[0], pages.shape[1]
    g = pages[layer, jnp.arange(hk)[None, :, None], table[:, None, :]]
    # the barrier hands the attention contraction this (B, S, Hkv, D) tensor
    # as the dense slot-row path does: folded into the contraction instead,
    # the head transpose changes its summation order on some backends
    return jax.lax.optimization_barrier(
        g.reshape(b, hk, -1, cfg.head_dim).transpose(0, 2, 1, 3))


def _overlay(g: Array, new: Array, p0: Array) -> Array:
    """Lay ``new`` (B, T, Hkv, D), the K or V of positions p0..p0+T-1 of
    each lane (``p0`` (B,)), over the gathered ``g`` (B, S, Hkv, D): the
    tensor the pool would gather had those positions been written first."""
    t = new.shape[1]
    rel = jnp.arange(g.shape[1])[None, :] - p0[:, None]          # (B, S)
    inside = ((rel >= 0) & (rel < t))[:, :, None, None]
    if t > 1:
        new = jnp.take_along_axis(
            new, jnp.clip(rel, 0, t - 1)[:, :, None, None], axis=1)
    return jnp.where(inside, new, g)


def _paged_kv(cache: dict, table: Array, k: Array, v: Array, p0: Array,
              cfg: ModelConfig):
    """The layer's gathered (B, S, Hkv, D) K and V with ``k``/``v`` (B, T,
    Hkv, D), the K/V of positions p0..p0+T-1, laid in."""
    return tuple(_overlay(_gather_pages(cache[n], cache["layer"], table, cfg),
                          new, p0) for n, new in (("k", k), ("v", v)))


def _page_views(cache: dict, cfg: ModelConfig):
    """The layer's K and V pools as (P, page, Hkv, D) page views: what the
    Pallas kernels read."""
    return tuple(page_view(cache[n][cache["layer"]], cfg) for n in ("k", "v"))


def write_pages(pages: Array, new: Array, table: Array, p0: Array,
                active: Optional[Array] = None) -> Array:
    """Write every layer's K or V of T consecutive positions per lane into
    the stacked row pool, as one read-modify-write of the touched rows.

    pages: (L, Hkv, P, page*D); new: (L, B, T, Hkv, D), lane b's rows at
    positions p0[b]..p0[b]+T-1; table: (B, maxp). Positions past the table
    and every position of a lane that ``active`` (B,) marks False are not
    written; a touched page that takes no write is routed to the null page,
    which absorbs the rewrite, so only pages a lane owns are changed.
    """
    n_l, b, t, hk, hd = new.shape
    page = pages.shape[-1] // hd
    maxp = table.shape[1]
    n = (t + page - 2) // page + 1                     # pages T positions span
    lpage = p0[:, None] // page + jnp.arange(n)[None, :]          # (B, n)
    rel = (lpage[:, :, None] * page + jnp.arange(page)
           - p0[:, None, None]).reshape(b, n * page)   # index into new
    hit = (rel >= 0) & (rel < t)
    if active is not None:
        hit &= active[:, None]
    hit = hit.reshape(b, n, page) & (lpage < maxp)[:, :, None]
    phys = jnp.take_along_axis(table, jnp.clip(lpage, 0, maxp - 1), axis=1)
    phys = jnp.where(hit.any(axis=2), phys, 0).reshape(1, 1, b * n)
    # index rows by (layer, head, page): a scatter over pages alone, with the
    # other dims in its window, makes the compiler re-lay the pool out for it
    layer = jnp.arange(n_l)[:, None, None]
    head = jnp.arange(hk)[None, :, None]
    vals = jnp.take_along_axis(
        jnp.moveaxis(new, 3, 1),                       # (L, Hkv, B, T, D)
        jnp.clip(rel, 0, t - 1)[None, None, :, :, None], axis=3)
    rows = pages[layer, head, phys].reshape(n_l, hk, b, n, page, hd)
    rows = jnp.where(hit[None, None, :, :, :, None],
                     vals.reshape(n_l, hk, b, n, page, hd), rows)
    return pages.at[layer, head, phys].set(rows.reshape(n_l, hk, b * n, -1))


def attention_decode_paged(params, x: Array, cfg: ModelConfig, cache: dict,
                           pos: Array, table: Array, active: Array,
                           backend: str = "xla"):
    """One-token decode against a paged KV cache.

    cache: the layer's view of the stack's page pools, read only (above);
    ``pos`` (B,) is each slot's cache position; ``table`` (B, maxp) the block
    table; ``active`` (B,) marks the lanes whose write the stack makes
    (inactive lanes write nothing, so a garbage lane can never dirty a page a
    mid-prefill slot already owns). Returns (out, {'k','v': (B, 1, Hkv, D)}):
    the new token's K/V, which ``write_pages`` puts at ``pos`` after the scan.

    ``backend`` picks the attention compute: ``"xla"`` gathers the pages into a
    dense (B, maxp*page, ...) K/V with the new token laid in at ``pos`` and
    runs the masked-softmax oracle (bitwise the dense slot-row path);
    ``"pallas"`` / ``"pallas_interpret"`` run the ``kernels.paged_attention``
    scalar-prefetch kernel on the (P, page, Hkv, D) view with this tick's
    writes made — the block table becomes the DMA schedule and no contiguous
    K/V tensor ever exists. Both see the same K/V, so the backends can be
    swapped mid-stream.
    """
    b = x.shape[0]
    pos = jnp.asarray(pos)
    q, k, v = _qkv(params, x, cfg, pos[:, None])
    if backend != "xla":
        # deferred import: layers must stay importable without the kernel pkg
        from ..kernels.paged_attention import paged_attention as paged_kernel
        ck, cv = _page_views(cache, cfg)
        page = ck.shape[1]
        pidx = jnp.where(active, table[jnp.arange(b), pos // page], 0)
        off = pos % page
        out = paged_kernel(q[:, 0], ck.at[pidx, off].set(k[:, 0]),
                           cv.at[pidx, off].set(v[:, 0]), table,
                           (pos + 1).astype(jnp.int32),
                           interpret=backend == "pallas_interpret")
        out = out.reshape(b, 1, -1).astype(v.dtype)
    else:
        gk, gv = _paged_kv(cache, table, k, v, pos, cfg)
        kpos = jnp.arange(gk.shape[1])[None, :]
        mask = kpos <= pos[:, None]                    # (B, S)
        out = _sdpa(q, gk, gv, mask[:, None, :], cfg)
    return out @ params["wo"], {"k": k, "v": v}


def attention_verify_paged(params, x: Array, cfg: ModelConfig, cache: dict,
                           pos: Array, table: Array, active: Array,
                           backend: str = "xla"):
    """Batched k-position verify step against the paged KV cache
    (self-speculative decoding).

    x: (B, Sq, D) — row 0 is the slot's last emitted token at cache position
    ``pos`` (exactly what the next decode tick would feed), rows 1..Sq-1 the
    draft tokens at ``pos+1..pos+Sq-1``. Every row attends causally up to
    itself over the pages with the rows' own K/V laid in, and returns that
    K/V (B, Sq, Hkv, D) for the stack to write (inactive slots and positions
    past the block table are not written) — per-row this is bitwise the
    computation ``attention_decode_paged`` would run at that position with
    that K/V prefix resident, which is the whole accept-oracle argument.
    Positions the accept loop rejects hold draft K/V afterwards; they are
    only ever read masked and are overwritten by the next tick's writes
    before becoming visible.
    """
    b, sq, _ = x.shape
    pos = jnp.asarray(pos)
    lpos = pos[:, None] + jnp.arange(sq)[None, :]       # (B, Sq) absolute
    q, k, v = _qkv(params, x, cfg, lpos)
    if backend != "xla":
        # deferred import: layers must stay importable without the kernel pkg
        from ..kernels.paged_attention import paged_attention_verify
        ck, cv = _page_views(cache, cfg)
        page, maxp = ck.shape[1], table.shape[1]
        # each row lands at its own position; inactive lanes and rows past
        # the table's capacity go to the null/trash page
        writable = active[:, None] & (lpos < maxp * page)
        pidx = jnp.take_along_axis(table, jnp.clip(lpos // page, 0, maxp - 1),
                                   axis=1)
        pidx = jnp.where(writable, pidx, 0)
        off = lpos % page
        out = paged_attention_verify(q, ck.at[pidx, off].set(k),
                                     cv.at[pidx, off].set(v), table,
                                     pos.astype(jnp.int32),
                                     interpret=backend == "pallas_interpret")
        out = out.reshape(b, sq, -1).astype(v.dtype)
    else:
        gk, gv = _paged_kv(cache, table, k, v, pos, cfg)
        kpos = jnp.arange(gk.shape[1])[None, None, :]
        mask = kpos <= lpos[:, :, None]                # (B, Sq, S)
        out = _sdpa(q, gk, gv, mask, cfg)
    return out @ params["wo"], {"k": k, "v": v}


def attention_prefill_paged(params, x: Array, cfg: ModelConfig, cache: dict,
                            table_row: Array, p0: Array):
    """One prefill *chunk* (batch-of-1) against the slot's pages.

    x: (1, C, D) — the chunk's embeddings; ``table_row`` (maxp,) the slot's
    block table row; ``p0`` the chunk's first absolute position. Queries attend
    over the gathered pages (fixed maxp*page == max_cache length) with the
    chunk's K/V laid in, so every chunk call compiles one shape regardless of
    prompt length — and, because padded/garbage positions are masked to exact
    zeros, the result is bitwise identical to the one-shot full-sequence
    prefill. Returns (out, {'k','v': (1, C, Hkv, D)}), the chunk's K/V for
    the stack to write.
    """
    return attention_prefill_paged_multi(params, x, cfg, cache,
                                         table_row[None], jnp.reshape(p0, (1,)))


def attention_prefill_paged_multi(params, x: Array, cfg: ModelConfig,
                                  cache: dict, tables: Array, p0s: Array):
    """``J`` concurrent prefill chunks, one per lane, in a single call.

    x: (J, C, D) — each lane is one in-flight chunked-prefill job's chunk;
    ``tables`` (J, maxp) each lane's block-table row; ``p0s`` (J,) each chunk's
    first absolute position. Lanes write into disjoint page sets (the allocator
    guarantees a writable page has exactly one owner), except padding lanes,
    whose all-null tables route every write to the null/trash page. Each lane's
    math is row-independent and shape-identical to the single-job path, so
    batching jobs costs no exactness — it just turns J prefill dispatches per
    tick into one. Returns (out, {'k','v': (J, C, Hkv, D)}).
    """
    j, c, _ = x.shape
    lpos = p0s[:, None] + jnp.arange(c)[None, :]       # (J, C) absolute
    q, k, v = _qkv(params, x, cfg, lpos)
    gk, gv = _paged_kv(cache, tables, k, v, p0s, cfg)
    kpos = jnp.arange(gk.shape[1])[None, None, :]
    mask = kpos <= lpos[:, :, None]                    # (J, C, S)
    out = _sdpa(q, gk, gv, mask, cfg)
    return out @ params["wo"], {"k": k, "v": v}


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------
def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    dt = dtype_of(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": dense_init(k1, d, f, dt),
        "w_up": dense_init(k2, d, f, dt),
        "w_down": dense_init(k3, f, d, dt),
    }


def mlp(params, x: Array, cfg: ModelConfig) -> Array:
    act = jax.nn.silu if cfg.act == "silu" else jax.nn.gelu
    h = constrain(act(x @ params["w_gate"]) * (x @ params["w_up"]),
                  DP, None, "model")
    return h @ params["w_down"]
