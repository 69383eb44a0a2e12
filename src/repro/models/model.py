"""Top-level model API: init / train loss / prefill / decode, uniform across families.

Batch layouts (all fields optional per family):
    train:   {"tokens": (B,S) i32, "patches": (B,P,Fd), "frames": (B,S,Fd)}
    decode:  {"token": (B,1) i32, "frame": (B,1,Fd)} + cache + pos

Losses are next-token cross-entropy in fp32; for VLM the loss is masked to the text
positions (the patch prefix carries no labels).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import frontends, layers, transformer
from .layers import dtype_of, embed_init, init_rmsnorm, rmsnorm

Array = jax.Array


class TrainOut(NamedTuple):
    loss: Array
    aux_loss: Array                # Switch aux loss (0 unless router_mode == 'aux')
    load_frac: Optional[Array]     # (L, E) per-layer expert load fractions
    drop_frac: Array
    logits_mean_abs: Array         # cheap NaN/scale canary


class SamplingSpec(NamedTuple):
    """Per-lane sampling arrays for a batch of decode lanes (jit-friendly).

    One row per lane — a slot of the serving engine's pool or a sequence of a
    one-shot batch. Both backends feed the same rows through the same
    ``sample_tokens`` lane, which is what makes engine-vs-oneshot token parity
    hold bitwise for seeded sampling."""

    keys: Array         # (B, 2) uint32 per-lane base PRNG keys
    temperature: Array  # (B,) f32; 0 => exact argmax (the greedy lane)
    top_k: Array        # (B,) i32; 0 => disabled
    top_p: Array        # (B,) f32; 1.0 => disabled
    rep_penalty: Array  # (B,) f32; 1.0 => disabled (repetition penalty)
    pres_penalty: Array  # (B,) f32; 0.0 => disabled (presence penalty)
    freq_penalty: Array  # (B,) f32; 0.0 => disabled (frequency penalty)


def init_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype_of(cfg)),
        "stack": transformer.init_stack(ks[1], cfg),
        "final_norm": init_rmsnorm(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["head"] = embed_init(ks[2], cfg.vocab_size, cfg.d_model, dtype_of(cfg))
    if cfg.frontend_dim:
        p["frontend"] = frontends.init_frontend(ks[3], cfg)
    return p


def _embed(params, cfg: ModelConfig, tokens: Array) -> Array:
    # gather the (possibly fsdp-sharded) table at use, keep activations on DP
    table = layers.constrain(params["embed"], "model", None)
    x = table[tokens]
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(cfg.d_model).astype(x.dtype)
    return layers.constrain(x, layers.DP, None, None)


def _head(params, cfg: ModelConfig, x: Array) -> Array:
    x = rmsnorm(params["final_norm"], x, cfg)
    table = params["embed"] if cfg.tie_embeddings else params["head"]
    table = layers.constrain(table, "model", None)
    logits = (x @ table.T).astype(jnp.float32)
    return layers.constrain(logits, layers.DP, None, "model")


def _inputs_train(params, cfg: ModelConfig, batch: dict):
    """Returns (x, prefix_len, label_mask_offset)."""
    if cfg.family == "vlm":
        prefix = frontends.project_frontend(params["frontend"], batch["patches"])
        text = _embed(params, cfg, batch["tokens"])
        x = jnp.concatenate([prefix, text], axis=1)
        return x, prefix.shape[1]
    if cfg.family == "audio":
        # EnCodec frame embeddings (stub frontend) + code-token embeddings
        x = _embed(params, cfg, batch["tokens"]) \
            + frontends.project_frontend(params["frontend"], batch["frames"])
        return x, 0
    return _embed(params, cfg, batch["tokens"]), 0


def train_loss(params, cfg: ModelConfig, batch: dict,
               router_bias: Optional[Array] = None) -> TrainOut:
    tokens = batch["tokens"]
    x, plen = _inputs_train(params, cfg, batch)
    prefix_len = jnp.asarray(plen) if plen else None
    x, load, aux, drop = transformer.apply_stack(params["stack"], x, cfg,
                                                 bias=router_bias,
                                                 prefix_len=prefix_len)
    x = x[:, plen:] if plen else x
    logits = _head(params, cfg, x)                      # (B, S, V) fp32

    targets = tokens[:, 1:]
    lg = logits[:, :-1]
    # vocab-sharded-friendly CE: logsumexp + one-hot contraction (no gather over the
    # TP-sharded vocab dim — a take_along_axis would all-gather the full logits)
    lse = jax.nn.logsumexp(lg, axis=-1)
    correct = jnp.sum(lg * jax.nn.one_hot(targets, lg.shape[-1], dtype=lg.dtype),
                      axis=-1)
    loss = jnp.mean(lse - correct)
    if cfg.router_mode == "aux" and cfg.num_experts:
        loss = loss + cfg.aux_loss_coef * aux
    return TrainOut(loss=loss, aux_loss=aux, load_frac=load, drop_frac=drop,
                    logits_mean_abs=jnp.mean(jnp.abs(lg)))


def penalize_logits(lg: Array, spec: SamplingSpec, counts: Array) -> Array:
    """Apply per-lane repetition / presence / frequency penalties to (B, V)
    logits given ``counts`` (B, V) — how often each vocab id has been
    *generated* by the lane's request so far (prompt tokens are not counted;
    the seed token is, once emitted). Lanes with all three penalties at their
    neutral values (1.0 / 0.0 / 0.0) are returned **bitwise unchanged** via a
    per-lane ``where`` — the penalty-free path cannot drift by construction,
    which is what keeps the parity oracle's greedy claims intact."""
    cnt = counts.astype(jnp.float32)
    counted = cnt > 0
    rep = spec.rep_penalty[:, None]
    scaled = jnp.where(lg > 0, lg / rep, lg * rep)
    pen = jnp.where(counted, scaled, lg) \
        - spec.freq_penalty[:, None] * cnt \
        - spec.pres_penalty[:, None] * counted.astype(jnp.float32)
    neutral = ((spec.rep_penalty == 1.0) & (spec.pres_penalty == 0.0)
               & (spec.freq_penalty == 0.0))
    return jnp.where(neutral[:, None], lg, pen)


def sample_tokens(logits: Array, spec: SamplingSpec, step,
                  counts: Optional[Array] = None) -> Array:
    """Sample one token per lane from last-position ``logits`` ((B, V) or
    (B, T, V), last position used) under per-lane ``SamplingSpec`` rows.

    The per-step key is ``fold_in(lane key, step)`` where ``step`` is the
    index of the token being emitted (scalar or (B,) — the engine passes each
    slot's emitted-token count, the one-shot loop its scan index), so a
    request's key stream is a function of its params and its own progress
    only, never of what shares the batch. Each lane is row-wise — scale by
    temperature, full descending sort, top-k rank mask, top-p cumulative-mass
    mask (the top token always survives), Gumbel draw over the survivors — so
    a lane's token is bitwise independent of batch composition; temperature-0
    lanes short out to the exact ``argmax`` the greedy path takes.

    ``counts`` (B, V) switches on the repetition/presence/frequency penalty
    lane (:func:`penalize_logits`) ahead of both the greedy argmax and the
    sampled draw; penalty-free lanes stay bitwise on the unpenalized path."""
    lg = logits[:, -1] if logits.ndim == 3 else logits          # (B, V) fp32
    if counts is not None:
        lg = penalize_logits(lg, spec, counts)
    b, v = lg.shape
    greedy_tok = jnp.argmax(lg, axis=-1)
    keys = jax.vmap(jax.random.fold_in)(
        spec.keys, jnp.broadcast_to(jnp.asarray(step, jnp.int32), (b,)))

    def lane(row, key, temp, k, p):
        scaled = row / jnp.maximum(temp, jnp.float32(1e-6))
        vals, idx = jax.lax.top_k(scaled, v)                    # full sort
        keep_k = jnp.arange(v) < jnp.where(k > 0, k, v)
        probs = jax.nn.softmax(vals)
        mass_before = jnp.cumsum(probs) - probs
        masked = jnp.where(keep_k & (mass_before < p), vals, -jnp.inf)
        return idx[jax.random.categorical(key, masked)]

    sampled = jax.vmap(lane)(lg, keys, spec.temperature, spec.top_k,
                             spec.top_p)
    tok = jnp.where(spec.temperature > 0, sampled, greedy_tok)
    return tok[:, None].astype(jnp.int32)                       # (B, 1)


def chosen_logprob(logits: Array, tok: Array) -> Array:
    """Logprob of each lane's chosen token under the *raw* model distribution
    (log-softmax of the unscaled fp32 logits — independent of temperature /
    top-k / top-p, so greedy and sampled lanes report on the same scale).
    ``logits`` (B, V) or (B, T, V) (last position used), ``tok`` (B, 1);
    returns (B, 1) fp32. Pure row-wise math on the logits lane both backends
    already hold, so the value is bitwise identical engine-vs-oneshot wherever
    the logits are."""
    lg = logits[:, -1] if logits.ndim == 3 else logits          # (B, V) fp32
    lp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(lp, tok.astype(jnp.int32), axis=-1)


def init_cache(cfg: ModelConfig, batch: int, s_max: int) -> dict:
    return {
        "layers": transformer.init_stack_cache(cfg, batch, s_max, dtype_of(cfg)),
        "pos": jnp.zeros((), jnp.int32),
    }


def prefill(params, cfg: ModelConfig, batch: dict, cache: dict,
            router_bias: Optional[Array] = None):
    """Batched prompt processing; fills the cache and returns last-position logits."""
    x, plen = _inputs_train(params, cfg, batch)
    prefix_len = jnp.asarray(plen) if plen else None
    x, layer_caches = transformer.apply_stack_prefill(
        params["stack"], x, cfg, cache["layers"], bias=router_bias,
        prefix_len=prefix_len)
    logits = _head(params, cfg, x[:, -1:])
    new_cache = {"layers": layer_caches,
                 "pos": jnp.asarray(x.shape[1], jnp.int32)}
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, batch: dict, cache: dict,
                router_bias: Optional[Array] = None,
                table: Optional[Array] = None,
                active: Optional[Array] = None,
                attn_backend: str = "xla"):
    """One-token step for every sequence in the batch. Returns (logits, new_cache).

    ``table`` (B, maxp) switches full-attention layers onto the paged KV pool.
    ``active`` (B,) additionally freezes the *slot-row* caches (recurrent
    state, ring buffers) of inactive slots: a garbage lane must never advance
    state a chunked prefill is threading through that row between ticks. The
    paged leaves don't need the freeze — inactive lanes write no page
    (``layers.write_pages``). ``attn_backend`` picks the paged attention
    compute: ``"xla"`` (dense gather oracle) or
    ``"pallas"`` / ``"pallas_interpret"`` (the block-table Pallas kernel)."""
    x = _embed(params, cfg, batch["token"])
    if cfg.family == "audio":
        x = x + frontends.project_frontend(params["frontend"], batch["frame"])
    x, layer_caches = transformer.apply_stack_decode(
        params["stack"], x, cfg, cache["layers"], cache["pos"], bias=router_bias,
        table=table, active=active, attn_backend=attn_backend)
    if active is not None:
        def freeze(kind, new, old):
            if kind in ("attn", "moe"):
                return new
            return jax.tree.map(
                lambda n, o: jnp.where(
                    active.reshape((1, -1) + (1,) * (n.ndim - 2)), n, o),
                new, old)
        layer_caches = transformer.map_block_caches(cfg, freeze, layer_caches,
                                                    cache["layers"])
    logits = _head(params, cfg, x)
    return logits, {"layers": layer_caches, "pos": cache["pos"] + 1}


def verify_step(params, cfg: ModelConfig, batch: dict, pool: dict,
                table: Array, active: Optional[Array] = None,
                attn_backend: str = "xla",
                router_bias: Optional[Array] = None):
    """Batched k-position verify step for self-speculative decoding.

    ``batch["tokens"]`` is (B, K+1): each slot's last emitted token followed by
    its K draft proposals. Row ``j`` is scored at cache position
    ``pool["pos"] + j`` with causal access up to itself — one forward pass
    whose per-row logits are bitwise what ``decode_step`` would produce row by
    row (same gather + ``_sdpa`` contraction per query row, dropless MoE).
    K/V for every row is written into the paged pool as a side effect, so an
    accepted prefix's cache is exactly what sequential decode would have left.

    Returns ``(logits (B, K+1, V), new_pool)``; ``pos`` is left untouched —
    the engine owns position advancement from its host-side accept loop."""
    x = _embed(params, cfg, batch["tokens"])
    x, layer_caches = transformer.apply_stack_verify(
        params["stack"], x, cfg, pool["layers"], pool["pos"],
        bias=router_bias, table=table, active=active,
        attn_backend=attn_backend)
    logits = _head(params, cfg, x)
    return logits, {"layers": layer_caches, "pos": pool["pos"]}


# ---------------------------------------------------------------------------
# paged slot-pool surgery (block-table KV cache, continuous-batching engine)
# ---------------------------------------------------------------------------
def init_slot_cache_paged(cfg: ModelConfig, num_slots: int, s_max: int,
                          num_pages: int, page_size: int) -> dict:
    """Paged pooled decode cache: full-attention K/V live in per-layer physical
    page pools (Hkv, num_pages, page_size*D), one row per KV head and page,
    indexed by a host-side block table; recurrent/ring leaves stay
    slot-indexed. ``pos`` is per-slot — like ``init_cache`` but a
    (num_slots,) vector, so slots can sit at different
    depths of their own sequences while sharing one compiled decode step. (The
    pre-paging fixed-row layout is the degenerate page_size == s_max config.)"""
    return {"layers": transformer.init_stack_cache_paged(
                cfg, num_slots, s_max, num_pages, page_size, dtype_of(cfg)),
            "pos": jnp.zeros((num_slots,), jnp.int32)}


def insert_slot_cache_paged(pool: dict, one: dict, cfg: ModelConfig,
                            slot: Array, table_row: Array) -> dict:
    """Splice a one-shot prefilled batch-of-1 dense cache into the paged pool.

    Full-attention leaves: the dense (1, s_max, Hkv, D) row is cut into
    (Hkv, maxp, page*D) page rows and scattered to the slot's physical pages;
    rows beyond the slot's allocation land on the null page (table entries
    there point at it), which is by construction write-don't-care. Other
    leaves are whole-row copies at the slot's batch index, erasing any stale
    state from the slot's previous occupant."""
    def splice(kind, full_d, one_d):
        if kind in ("attn", "moe"):
            def pagewise(full, o):
                reps, hk, _, row = full.shape
                rows = o.astype(full.dtype).reshape(reps, -1, row // o.shape[-1],
                                                    hk, o.shape[-1])
                rows = jnp.moveaxis(rows, 3, 1).reshape(reps, hk, -1, row)
                return full.at[:, :, table_row].set(rows)
            return jax.tree.map(pagewise, full_d, one_d)
        return jax.tree.map(
            lambda full, o: jax.lax.dynamic_update_slice_in_dim(
                full, o.astype(full.dtype), slot, axis=1), full_d, one_d)

    layer_caches = transformer.map_block_caches(cfg, splice, pool["layers"],
                                                one["layers"])
    return {"layers": layer_caches,
            "pos": pool["pos"].at[slot].set(one["pos"].astype(pool["pos"].dtype))}


def release_slot_cache_paged(pool: dict, cfg: ModelConfig, slot: Array) -> dict:
    """Retire a slot in the paged pool: zero the slot-row (recurrent/ring)
    leaves and the position; physical pages are NOT zeroed — they just return
    to the host free list, and stale contents are only ever read masked."""
    def wipe(kind, full_d):
        if kind in ("attn", "moe"):
            return full_d
        return jax.tree.map(lambda full: full.at[:, slot].set(0), full_d)

    layer_caches = transformer.map_block_caches(cfg, wipe, pool["layers"])
    return {"layers": layer_caches, "pos": pool["pos"].at[slot].set(0)}


def prefill_chunk(params, cfg: ModelConfig, batch: dict, pool: dict,
                  table_row: Array, p0: Array, last_idx: Array, slot: Array,
                  router_bias: Optional[Array] = None):
    """One chunk of a chunked prefill, written straight into the paged pool.

    ``batch`` holds the chunk's tokens (1, C) (+ frames for audio); ``p0`` is
    the chunk's first absolute position and ``last_idx`` the in-chunk index of
    the prompt's final token (only meaningful on the last chunk — the logits
    returned there seed decoding). The pool's ``pos`` is left untouched; the
    engine activates the slot when the final chunk lands."""
    x = _embed(params, cfg, batch["tokens"])
    if cfg.family == "audio":
        x = x + frontends.project_frontend(params["frontend"], batch["frames"])
    x, layer_caches = transformer.apply_stack_prefill_chunk(
        params["stack"], x, cfg, pool["layers"], table_row, p0, slot,
        bias=router_bias)
    logits = _head(params, cfg,
                   jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1))
    return logits, {"layers": layer_caches, "pos": pool["pos"]}


def prefill_chunk_multi(params, cfg: ModelConfig, batch: dict, pool: dict,
                        tables: Array, p0s: Array, last_idx: Array,
                        router_bias: Optional[Array] = None):
    """J concurrent prefill chunks (one in-flight job per lane) in one call.

    ``batch["tokens"]`` is (J, C); ``tables`` (J, maxp) each lane's block-table
    row; ``p0s`` (J,) each chunk's first absolute position; ``last_idx`` (J,)
    the in-chunk index of each prompt's final token (meaningful on a lane's
    last chunk — the logits there seed its decoding). Attention-stack configs
    only: lanes share no slot-row state, so J jobs cost one dispatch instead
    of J without changing any lane's math. Padding lanes carry an all-null
    table. The pool's ``pos`` is untouched; the engine activates each slot as
    its final chunk lands."""
    x = _embed(params, cfg, batch["tokens"])
    x, layer_caches = transformer.apply_stack_prefill_chunk_multi(
        params["stack"], x, cfg, pool["layers"], tables, p0s, bias=router_bias)
    sel = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)   # (J, 1, d)
    logits = _head(params, cfg, sel)
    return logits, {"layers": layer_caches, "pos": pool["pos"]}


def copy_page_paged(pool: dict, cfg: ModelConfig, src: Array, dst: Array) -> dict:
    """Copy-on-write fork: duplicate physical page ``src`` into ``dst`` across
    every paged (full-attention) layer pool. The caller then redirects the
    forking slot's block table to ``dst`` and overwrites the tail; entries
    beyond the shared prefix carry the donor's stale K/V, which is only ever
    read masked (or overwritten by the fork owner's own writes)."""
    def cp(kind, full_d):
        if kind in ("attn", "moe"):
            return jax.tree.map(
                lambda full: full.at[:, :, dst].set(full[:, :, src]), full_d)
        return full_d

    return {"layers": transformer.map_block_caches(cfg, cp, pool["layers"]),
            "pos": pool["pos"]}


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def active_param_count(params, cfg: ModelConfig) -> int:
    """Per-token active parameters (MoE counts only k of E experts)."""
    total = param_count(params)
    if not cfg.num_experts:
        return total

    def expert_leaves(p):
        return sum(int(x.size) for name in ("w_gate", "w_up", "w_down")
                   for x in jax.tree.leaves(p.get(name, ())))

    expert_total = 0
    for seg in params["stack"]:
        for pos_params in seg:
            if isinstance(pos_params, dict) and "moe" in pos_params:
                expert_total += expert_leaves(pos_params["moe"])
    active_frac = cfg.experts_per_token / cfg.num_experts
    return int(total - expert_total * (1.0 - active_frac))
