"""Continuous-batching serving engine governed by the paper's immune primitives,
over a **paged KV cache** with **chunked prefill**.

``serve.decode.generate`` serves a *fixed* batch: every prompt prefills together
and every sequence decodes in lockstep until the longest finishes. Real traffic
is an open-loop arrival process, so the engine keeps a fixed pool of decode
**slots** and admits requests mid-stream; finished sequences retire and their
slot is reused. All slot state is arrays (per-slot cache position, last token,
active mask), so one compiled decode step serves every tick regardless of
occupancy.

Memory plane — the page-table layout:

  * Each full-attention layer's K/V is a physical **page pool**
    ``(Hkv, num_pages, page_size * D)``, one row per KV head and page
    (stacked over depth). Each compiled step's layer scan reads the pools
    and emits its new K/V, written into the pools once after the scan.
    Page 0 is the null/trash page: never allocated, absorbs rewrites of
    pages no lane writes, read only masked.
  * A host-side block table (``serve.paging.PageAllocator``) maps each slot's
    logical pages to physical ones; the device sees the dense
    ``(num_slots, max_pages_per_slot)`` int32 table each tick. With
    ``max_pages_per_slot = max_cache // page_size`` the gathered K/V length is
    exactly ``max_cache``, so the paged decode is bitwise-identical to the
    dense slot-row layout (null-page padding is masked to exact zeros).
  * Admission charges pages under one of two disciplines
    (``EngineConfig.admission_mode``). ``"reserve"`` (legacy) promises a
    request's worst-case page count (``ceil((prompt + decode budget) /
    page_size)``) up front, so decode can never stall — paid for in admission
    pessimism. ``"preempt"`` (default) admits on *current* pages (the padded
    prompt tail only); decode growth acquires pages on demand, and when the
    pool runs dry the engine **preempts** the lowest-immune-priority resident
    (anergic classes first, then over-budget, then highest remembered cost —
    the paper's suppression signal as victim selection): its pages release,
    it re-queues, and on re-admission it re-prefills its original prompt and
    *replays* its recorded tokens through decode (same lane keys, same
    fold_in indices), so a preempted-then-resumed request is token-bitwise-
    identical to an unpreempted run. Either way pages are appended lazily as
    prefill chunks land and decode crosses page boundaries; retirement
    returns pages with no zeroing or row compaction. Recurrent states and
    sliding-window ring buffers are O(1)/O(window) per slot and stay
    slot-indexed — only full attention carries sequence-length paging.
  * **Pinned prefix cache** (``EngineConfig.pin_pages > 0``): the allocator
    keeps full prompt-page chains resident after their refcounts hit zero,
    charged to a pin budget with immune-memory-weighted LRU eviction (the
    per-class adoption-value EMA scores which chains stay hot). A returning
    tenant minutes later adopts the pinned chain exactly like a live shared
    one — its prefill is O(unique tokens) across idle gaps, not just within
    a burst.
  * **Prefix sharing** (``EngineConfig.prefix_sharing``): the allocator keeps
    a refcounted index of full prompt pages keyed by their token content.
    Admission walks a new prompt through it and *adopts* every hit —
    refcount++ on a resident physical page instead of reserving and
    re-prefilling it — so a thousand requests behind one system prompt hold
    ONE copy of its KV and only pay prefill for their unshared tails:
    O(unique tokens), not O(total), in both compute and pages. This is the
    paper's immune memory applied to KV state — work the population has
    already seen is recognized and not re-paid. A partial last-page hit is
    adopted too and **copy-on-write forked** (fresh page + on-device copy of
    the shared entries) before the slot's first write into it; shared full
    pages are never written (decode writes land past the prompt), so only the
    fork ever copies. Sharing is gated to configs where K/V is a pure function
    of the token prefix (text-only attention/dropless-MoE stacks with chunked
    prefill); recurrent state, frontend-conditioned and one-shot-prefill
    families never share. Admission charges only the *unshared* pages against
    ``available()``, so a prefix-hot request is admissible even when the pool
    could not hold its worst case from the free list alone.

Compute plane — chunked prefill (``EngineConfig.prefill_chunk > 0``): long
prompts are sliced into decode-tick-sized chunks written straight into the
slot's pages, one chunk per engine tick, interleaved with the running decodes —
a long prefill no longer stalls occupied slots, and the engine compiles ONE
chunk shape instead of one prefill shape per prompt length. Chunking applies
where it is bitwise-exact (attention stacks; MoE at dropless expert capacity;
SSM via state-resume when lengths align to ``ssm_chunk``); VLM prefix-LM,
finite-capacity MoE, and RG-LRU hybrids fall back to one-shot prefill. With
``prefill_streams > 1`` (attention stacks only), up to that many in-flight
prefill jobs advance per tick in ONE batched compiled call — concurrent long
prompts no longer serialize chunk-per-tick behind each other. Decode runs the
paged attention through ``EngineConfig.attn_backend``: the XLA gather
fallback, or the ``kernels.paged_attention`` Pallas kernel ("pallas" on TPU,
"pallas_interpret" anywhere) whose scalar-prefetch block-table index maps turn
the gather into the DMA schedule itself.

Admission is the immune loop applied to serving, per the anticipation argument
of Boulmier et al. (PAPERS.md) — schedule on *remembered* cost, not
instantaneous load:

  * ``ImmuneMemory``      — EMA of per-request-class decode cost (slot-ticks);
                            admission orders candidates by remembered cost, so
                            a class's history, not the current queue snapshot,
                            decides who gets a slot under pressure.
  * ``TwoStageRegulator`` — admission-burst throttle: a burst admits at full
                            speed (fast response), the suppressor population
                            then builds and pauses follow-on admissions
                            (delayed negative feedback), damping convoys.
  * ``AnergyGate``        — request classes that repeatedly blow their latency
                            budget without co-stimulation (in-budget
                            completions) become anergic and are shed (left in
                            the queue, not admitted); an IL-2-like signal
                            revives them when queue pressure drops.

A request whose prompt can never fit a slot is rejected at ``submit`` (counted
in ``stats()['rejected']``, against goodput) instead of raising; a request that
fits but finds no free pages is simply deferred in the queue until pages free
up — out-of-pages backpressure, not an error.

Requests arrive as ``serve.api.ServeRequest`` — prompt + ``SamplingParams``
(temperature/top-p/top-k/seed, token budget, stop ids) + scheduling metadata —
and progress leaves as ``serve.api.RequestOutput`` deltas from ``stream()``.
Sampling runs *inside* the one compiled decode step: per-slot lane arrays
(``models.model.SamplingSpec``) ride next to ``last``/``active``, each lane's
key folds with its slot's emitted-token count, and ``model.sample_tokens``
applies the masked top-k/top-p draw on the logits lane — the same lane math
(and key discipline) as one-shot ``serve.api.generate``, so a seeded request
emits identical tokens on either backend and temperature-0 lanes stay bitwise
argmax. Retirement is per-request: token budget or any of the request's stop
ids (``Engine._finished`` records the ``finish_reason``), and pages free the
same tick.

The FIFO policy (``EngineConfig(policy="fifo")``) is the baseline the
benchmark compares against; ``page_size == max_cache`` degenerates to the
fixed-row engine (one page per slot, reserved whole at admission) for
equal-memory comparisons.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from dataclasses import dataclass
from functools import partial
from typing import Iterator, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..core import immune
from ..models import layers, model, transformer
from . import groups
from . import spec as specdec
from .api import (RequestOutput, SamplingParams, ServeRequest,  # noqa: F401
                  spec_for)
from .decode import greedy, null_spec
from .paging import OutOfPages, PageAllocator, pages_for
from .spans import (ADMIT, DECODE, IMMUNE, PAGES, PREFILL, RETIRE, STEP,
                    pull, span)

Array = jax.Array


class EngineConfig(NamedTuple):
    num_slots: int = 4
    max_cache: int = 96               # per-slot logical KV capacity (tokens)
    policy: str = "immune"            # "immune" | "fifo"
    num_classes: int = 4
    # Unit discipline: latency_budget is engine TICKS and is only ever compared
    # against tick latencies (finish_tick - arrival); a per-request
    # ServeRequest.deadline is wall-clock SECONDS and is only ever compared
    # against wall-clock latencies (finish_time - submit_time). One unit per
    # comparison — see Engine._slo.
    latency_budget: float = 32.0      # ticks; beyond this a completion "blew" SLO
    mem_decay: float = 0.8            # cost-memory EMA decay
    reg_threshold: float = 2.0        # admission pauses while response exceeds this
    shed_level: float = 0.5           # anergy level above which a class is shed
    low_pressure: float = 0.5         # queue_len < low_pressure*num_slots -> IL-2
    anergy_onset: float = 0.34
    anergy_revival: float = 0.3
    # -- paged KV plane ------------------------------------------------------
    page_size: int = 16               # tokens per physical page
    num_pages: Optional[int] = None   # pool size incl. the null page; None ->
    #                                   fully provisioned (slots*maxp + 1),
    #                                   admission-equivalent to fixed rows
    prefill_chunk: int = 0            # >0: chunked prefill, one chunk per tick
    prefix_sharing: bool = True       # refcounted prompt-prefix page sharing
    attn_backend: str = "xla"         # "xla" | "pallas" | "pallas_interpret"
    prefill_streams: int = 1          # >1: batch that many prefill jobs/tick
    capture_logits: bool = False      # record per-token logits rows on each
    #                                   request (the logits parity oracle)
    # -- KV memory hierarchy -------------------------------------------------
    admission_mode: str = "preempt"   # "preempt": admit on current pages and
    #                                   evict the lowest-immune-priority slot
    #                                   when decode would stall; "reserve":
    #                                   legacy worst-case page reservation
    pin_pages: int = 0                # persistent prefix-cache budget: full
    #                                   prompt-page chains survive refcount
    #                                   zero as pinned entries (0 = off)
    # -- self-speculative decoding -------------------------------------------
    spec_decode: int = 0              # k: draft tokens proposed per spec tick
    #                                   (0 = off). Spec ticks run only on
    #                                   all-greedy resident batches with no
    #                                   penalties/logprobs; emitted tokens are
    #                                   bitwise the non-speculative stream's.
    spec_draft_layers: int = 0        # draft depth: leading layer repetitions
    #                                   of the SAME weights the draft pass
    #                                   runs (truncated-depth early exit);
    #                                   must be in (0, num_layers)


@dataclass
class _PrefillJob:
    """An in-flight chunked prefill: chunks land tick by tick while the other
    slots keep decoding; the slot activates when the last chunk lands. ``p0``
    starts past the shared prefix when admission adopted resident pages —
    only the unshared tail is ever computed."""
    req: ServeRequest
    slot: int
    p0: int          # next chunk's first absolute position
    total: int       # padded prompt end (p0 grid aligned to prefill_chunk)
    length: int      # true prompt length (incl. any frontend prefix)
    share: bool = False   # register this prompt's full pages on completion


# ---------------------------------------------------------------------------
# jitted slot-pool kernels — shared across Engine instances via jit's cache
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("cfg", "max_cache"))
def _prefill_one(params, cfg: ModelConfig, prompts: dict, max_cache: int,
                 router_bias):
    """Prefill a batch-of-1 prompt into a fresh dense cache; returns
    (last-position logits, cache). Identical math to the first stage of
    ``decode.generate`` — the parity anchor for the one-shot admission path;
    the logits seed decoding through ``_seed_token``."""
    cache = model.init_cache(cfg, 1, max_cache)
    logits, cache = model.prefill(params, cfg, prompts, cache,
                                  router_bias=router_bias)
    return logits, cache


@partial(jax.jit, static_argnames=("do_sample",))
def _seed_token(logits, spec, do_sample: bool):
    """First emitted token from a prompt's last-position logits: exact argmax
    on the greedy path, else the request's sampling lane at fold index 0 —
    the same draw one-shot ``decode.generate`` takes for its first token."""
    return model.sample_tokens(logits, spec, 0) if do_sample \
        else greedy(logits)


@jax.jit
def _chosen_lp(logits, tok):
    """Chosen-token logprob of a seed token (the per-request admission path;
    decoded tokens get theirs inside the compiled decode tick)."""
    return model.chosen_logprob(logits, tok)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0, 5))
def _splice(pool, one, slot, table_row, first, last, active, cfg: ModelConfig):
    """Insert a one-shot prefilled batch-of-1 cache + its first token into
    ``slot`` of the paged pool (K/V rows scattered to the slot's pages)."""
    pool = model.insert_slot_cache_paged(pool, one, cfg, slot, table_row)
    return pool, last.at[slot].set(first[0]), active.at[slot].set(True)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(3,))
def _prefill_chunk(params, cfg: ModelConfig, chunk: dict, pool, table_row, p0,
                   last_idx, slot, router_bias):
    """Land one prefill chunk in the slot's pages; returns (logits of the
    chunk's last real position, pool). One compiled shape per config; the
    logits only matter on the final chunk, where they seed decoding."""
    logits, pool = model.prefill_chunk(params, cfg, chunk, pool, table_row, p0,
                                       last_idx, slot, router_bias=router_bias)
    return logits, pool


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(3,))
def _prefill_chunks(params, cfg: ModelConfig, chunk: dict, pool, tables, p0s,
                    last_idxs, router_bias):
    """Land one chunk of up to ``prefill_streams`` concurrent prefill jobs in
    ONE compiled call (attention stacks only); lanes beyond the live job count
    are padding with all-null tables. Returns ((J, 1, V) logits, pool)."""
    logits, pool = model.prefill_chunk_multi(params, cfg, chunk, pool, tables,
                                             p0s, last_idxs,
                                             router_bias=router_bias)
    return logits, pool


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def _copy_page(pool, src, dst, cfg: ModelConfig):
    """Copy-on-write fork: duplicate physical page ``src`` into ``dst`` across
    every paged layer before the forking slot's first write into it."""
    return model.copy_page_paged(pool, cfg, src, dst)


@partial(jax.jit, donate_argnums=(0, 1))
def _activate(pool, last, active, slot, first, length):
    """Final chunk landed: set the slot's position, first token, active bit."""
    return ({"layers": pool["layers"], "pos": pool["pos"].at[slot].set(length)},
            last.at[slot].set(first[0]), active.at[slot].set(True))


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def _release(pool, active, slot, cfg: ModelConfig):
    """Retire ``slot``: zero its slot-row (recurrent/ring) state and position;
    its physical pages just return to the host free list, unzeroed."""
    return (model.release_slot_cache_paged(pool, cfg, slot),
            active.at[slot].set(False))


# pool and last are donated: the engine rebinds both from the return value each
# tick, and without donation every decoded token would pay a fresh copy of the
# whole pooled KV cache (the scan carry in decode._decode_loop gets this free)
@partial(jax.jit,
         static_argnames=("cfg", "attn_backend", "do_sample", "return_logits",
                          "return_logprobs", "use_penalties", "return_topk"),
         donate_argnums=(2, 3))
def _decode_tick(params, cfg: ModelConfig, pool, last, active, table,
                 router_bias, frames, spec, steps_done, pen_counts=None,
                 attn_backend="xla", do_sample=False, return_logits=False,
                 return_logprobs=False, use_penalties=False,
                 return_topk: int = 0):
    """One token for every slot (occupied or not) — the single compiled decode
    step. Inactive slots advance neither position nor state; their lane
    computes a garbage token that the host discards (inactive lanes write no
    page, slot-row caches are frozen), which keeps the step shape
    independent of occupancy AND keeps garbage
    lanes from dirtying pages a mid-flight chunked prefill already owns.
    ``attn_backend`` selects the paged attention compute (XLA gather vs the
    Pallas block-table kernel). With ``do_sample``, per-slot sampling runs on
    the logits lane in this same compiled step: ``spec`` carries each slot's
    key/temperature/top-k/top-p row and ``steps_done`` its emitted-token
    count (the fold_in index), so a lane's draw depends only on its own
    request — never on what shares the pool. The raw logits are returned for
    the capture-logits parity oracle."""
    batch = {"token": last}
    if cfg.family == "audio":
        batch["frame"] = frames
    logits, new_pool = model.decode_step(params, cfg, batch, pool,
                                         router_bias=router_bias,
                                         table=table, active=active,
                                         attn_backend=attn_backend)
    # repetition/presence/frequency penalties ride the sampling lane: a
    # per-lane where in model.penalize_logits keeps penalty-free lanes bitwise
    # on the unpenalized path, and greedy-with-penalties is the temperature-0
    # sampling lane (argmax of the penalized logits)
    nxt = model.sample_tokens(logits, spec, steps_done,
                              counts=pen_counts if use_penalties else None) \
        if do_sample else greedy(logits)             # (S, 1)
    pos = jnp.where(active, new_pool["pos"], pool["pos"])
    last = jnp.where(active[:, None], nxt, last)
    # silent-corruption guard: a NaN/Inf anywhere in a lane's logits means its
    # KV or activations are poisoned (bad page, bit flip, kernel bug) and the
    # sampled token is garbage — flag the lane so the host retires it as
    # "corrupted" instead of streaming the garbage on. A (S,)-bool reduction
    # over the logits already resident is noise next to the matmul that
    # produced them, so the guard is always on.
    ok = jnp.isfinite(logits).all(axis=(1, 2))
    # the (S, 1, V) logits are a jit output only when the parity oracle wants
    # them — otherwise returning them would materialize a vocab-sized buffer
    # per decoded token just for the host to drop. Chosen-token logprobs ride
    # in-step on the logits lane already resident (no extra vocab pass on the
    # host side) when any resident request asked for them.
    # top-k alternative logprobs ride in-step too (partial sort of the raw
    # log-softmax lane — the host slices each request's own k out of the
    # batch-wide max-k rows; a shorter prefix of a longer top_k is identical)
    topk = None
    if return_topk:
        lpf = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))
        topk = jax.lax.top_k(lpf, return_topk)       # ((S, k) vals, (S, k) ids)
    return (nxt, last, {"layers": new_pool["layers"], "pos": pos}, ok,
            logits if return_logits else None,
            model.chosen_logprob(logits, nxt) if return_logprobs else None,
            topk)


@partial(jax.jit, static_argnames=("k",))
def _topk_lp(logits, k: int):
    """Top-k alternative logprobs of a prefill's last-position logits (the
    seed token's row — decoded rows get theirs inside the compiled tick)."""
    lp = jax.nn.log_softmax(logits[:, -1].astype(jnp.float32))
    return jax.lax.top_k(lp, k)


# ---------------------------------------------------------------------------
# immune admission controller
# ---------------------------------------------------------------------------
class ImmuneAdmission:
    """Host-side admission controller over the three immune primitives.

    Per tick: completions feed the cost memory and the anergy
    stimulus/co-stimulus counters; ``end_tick`` advances the regulator (with the
    tick's admissions as stimulus) and the anergy gate (with IL-2 flowing when
    queue pressure is low). The states live on the device; every call runs
    inside an ``IMMUNE`` span, nested in its caller's."""

    def __init__(self, ecfg: EngineConfig):
        self.ecfg = ecfg
        c = ecfg.num_classes
        self.memory = immune.ImmuneMemory.create((c,), decay=ecfg.mem_decay)
        self.regulator = immune.TwoStageRegulator.create()
        self.reg_state = self.regulator.init(())
        self.gate = immune.AnergyGate.create(onset=ecfg.anergy_onset,
                                             revival=ecfg.anergy_revival)
        self.anergy = self.gate.init((c,))
        self._blown = np.zeros(c, np.float32)
        self._ok = np.zeros(c, np.float32)

    def remembered_cost(self, rclass: int) -> float:
        with span(IMMUNE):
            return float(pull(self.memory.value)[rclass])

    def anergy_level(self, rclass: int) -> float:
        with span(IMMUNE):
            return float(pull(self.anergy.level)[rclass])

    def observe_completion(self, rclass: int, cost: float, latency: float,
                           budget: Optional[float] = None):
        with span(IMMUNE):
            # per-class EMA: observing `value` for the untouched classes
            # leaves them unchanged under ImmuneMemory's decay*v +
            # (1-decay)*obs update
            self.memory = self.memory.update(
                self.memory.value.at[rclass].set(cost))
            if budget is None:
                budget = self.ecfg.latency_budget
            if latency > budget:
                self._blown[rclass] += 1.0
            else:
                self._ok[rclass] += 1.0

    def admissible(self, rclass: int) -> bool:
        return self.anergy_level(rclass) <= self.ecfg.shed_level

    def throttled(self) -> bool:
        with span(IMMUNE):
            return float(pull(self.reg_state.response)) \
                > self.ecfg.reg_threshold

    def degrade(self, classes, severity: float):
        """Fleet capacity loss as an immune stress signal (graceful
        degradation): drive the anergy gate toward shedding ``classes`` by
        applying antigen without co-stimulation, scaled by ``severity``
        (the router's view of how much of the fleet is dead). Called by the
        fleet router each tick a replica is down, so low-priority classes
        shed on the survivors before interactive traffic browns out; once
        capacity returns the stimulus stops and IL-2 revives the classes in
        the next quiet period — the same revival path as ordinary anergy."""
        c = self.ecfg.num_classes
        stim = np.zeros(c, np.float32)
        for k in classes:
            if 0 <= k < c:
                stim[k] = min(max(float(severity), 0.0), 1.0)
        with span(IMMUNE):
            self.anergy = self.gate.step(
                self.anergy, stimulus=jnp.asarray(stim),
                costimulus=jnp.zeros(c, jnp.float32), il2=0.0)

    def end_tick(self, admitted: int, queue_len: int,
                 queued_demand: np.ndarray, predicted_cost: np.ndarray):
        """Advance the regulator and anergy gate one tick.

        Anergy stimulus is anticipatory: a class with queued demand whose
        predicted cost already exceeds the latency budget *will* blow its SLO —
        that is antigen without co-stimulation, and waiting for the completions
        to prove it would let the convoy form first. In-budget completions are
        the co-stimulation; IL-2 flows when queue pressure drops, reviving shed
        classes so they are served in quiet periods."""
        with span(IMMUNE):
            stim = jnp.asarray(admitted / max(self.ecfg.num_slots, 1),
                               jnp.float32)
            self.reg_state = self.regulator.step(self.reg_state, stim)
            il2 = 1.0 if queue_len < self.ecfg.low_pressure \
                * self.ecfg.num_slots else 0.0
            will_blow = (queued_demand > 0) & \
                (predicted_cost > self.ecfg.latency_budget)
            self.anergy = self.gate.step(
                self.anergy,
                stimulus=jnp.asarray((self._blown > 0) | will_blow,
                                     jnp.float32),
                costimulus=jnp.asarray(self._ok > 0, jnp.float32),
                il2=il2)
            self._blown[:] = 0.0
            self._ok[:] = 0.0

    # -- durability ----------------------------------------------------------
    def export_state(self) -> dict:
        """JSON-able snapshot of the learned immune state: the per-class
        cost-memory EMA, the regulator populations, the anergy levels, and
        the tick-local SLO counters. Configuration (decays, thresholds) is
        NOT exported — it comes from the EngineConfig at restore."""
        def ls(tree):
            return [np.asarray(x).tolist() for x in jax.tree.leaves(tree)]
        return {"memory": np.asarray(self.memory.value).tolist(),
                "regulator": ls(self.reg_state),
                "anergy": ls(self.anergy),
                "blown": self._blown.tolist(), "ok": self._ok.tolist()}

    def import_state(self, d: dict) -> None:
        """Restore :meth:`export_state` output into this controller — the
        memory resumes warm instead of re-learning every class from zero."""
        def put(tree, vals):
            leaves, treedef = jax.tree.flatten(tree)
            return jax.tree.unflatten(treedef, [
                jnp.asarray(np.asarray(v, np.asarray(l).dtype).reshape(
                    np.shape(l))) for l, v in zip(leaves, vals)])
        self.memory = self.memory._replace(
            value=jnp.asarray(d["memory"], self.memory.value.dtype))
        self.reg_state = put(self.reg_state, d["regulator"])
        self.anergy = put(self.anergy, d["anergy"])
        self._blown = np.asarray(d["blown"], np.float32)
        self._ok = np.asarray(d["ok"], np.float32)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
class Engine:
    """Continuous-batching decode over a paged slot pool with queue admission."""

    def __init__(self, params, cfg: ModelConfig, ecfg: EngineConfig,
                 router_bias: Optional[Array] = None,
                 device: Optional[jax.Device] = None):
        """``device`` pins the engine to one chip: its params, page pool and
        slot state are placed there and every tick's uploads land there
        (``None``: the default device). A router gives each replica its own."""
        if ecfg.max_cache % ecfg.page_size:
            raise ValueError(f"max_cache {ecfg.max_cache} must be a multiple "
                             f"of page_size {ecfg.page_size}")
        if ecfg.prefill_chunk and ecfg.max_cache % ecfg.prefill_chunk:
            raise ValueError(f"max_cache {ecfg.max_cache} must be a multiple "
                             f"of prefill_chunk {ecfg.prefill_chunk}")
        if ecfg.attn_backend not in ("xla", "pallas", "pallas_interpret"):
            raise ValueError(f"unknown attn_backend {ecfg.attn_backend!r}")
        if ecfg.admission_mode not in ("preempt", "reserve"):
            raise ValueError(f"unknown admission_mode {ecfg.admission_mode!r}")
        if ecfg.spec_decode < 0:
            raise ValueError(f"spec_decode must be >= 0, got {ecfg.spec_decode}")
        if ecfg.spec_decode and not 0 < ecfg.spec_draft_layers < cfg.num_layers:
            raise ValueError(
                f"spec_draft_layers must be in (0, {cfg.num_layers}), got "
                f"{ecfg.spec_draft_layers}")
        self.device = device
        if device is not None:
            params, router_bias = jax.device_put((params, router_bias), device)
        self.params, self.cfg, self.ecfg = params, cfg, ecfg
        self.router_bias = router_bias
        # MoE: the decode tick runs every slot, occupied or not, and expert
        # capacity is contended across whatever shares the batch — a garbage
        # lane from an empty slot must never displace a real request's token.
        # Bump the decode-path capacity so the (tiny: num_slots * k) token set
        # is dropless by construction. Prefill keeps the configured capacity:
        # it is a batch-of-1 call, bitwise-identical to one-shot generate's.
        self.cfg_decode = cfg if not cfg.num_experts else dataclasses.replace(
            cfg, capacity_factor=float(max(cfg.num_experts,
                                           cfg.capacity_factor)))
        s = ecfg.num_slots
        self.maxp = ecfg.max_cache // ecfg.page_size
        num_pages = ecfg.num_pages if ecfg.num_pages is not None \
            else s * self.maxp + 1
        self.alloc = PageAllocator(
            num_pages, ecfg.page_size, s, self.maxp,
            share_prefix=ecfg.prefix_sharing, pin_pages=ecfg.pin_pages,
            num_classes=ecfg.num_classes, pin_decay=ecfg.mem_decay,
            require_reservation=(ecfg.admission_mode == "reserve"))
        kinds = set(transformer.layer_kinds(cfg))
        # prefix sharing is only sound where a position's K/V is a pure
        # function of the token prefix AND the unshared tail can run through
        # chunked prefill: text-only attention/dropless-MoE stacks
        self._share_ok = (ecfg.prefix_sharing and ecfg.prefill_chunk > 0
                          and kinds <= {"attn", "moe"}
                          and not cfg.frontend_dim and not cfg.frontend_tokens)
        # batched prefill streams need lanes with no slot-row state and no
        # per-position frontend inputs — same attention-stack gate
        self._multi_prefill = (ecfg.prefill_streams > 1
                               and kinds <= {"attn", "moe"}
                               and cfg.family not in ("audio", "vlm"))
        # self-speculative decoding: needs the k-position verify path (pure
        # attention/dropless-MoE stacks, no frontend inputs, no slot-row
        # state) and a single scan segment for the truncated-depth draft
        # slice. A router bias rides along (verify routes with exactly the
        # plain tick's bias). The per-tick gate additionally requires every
        # resident greedy with no penalties/logprobs — fold_in key and
        # penalty-count discipline are per-emitted-token, which a multi-token
        # tick cannot honor.
        self._spec_ok = (ecfg.spec_decode > 0
                         and 0 < ecfg.spec_draft_layers < cfg.num_layers
                         and kinds <= {"attn", "moe"}
                         and len(transformer.segments(cfg)) == 1
                         and not cfg.frontend_dim and not cfg.frontend_tokens
                         and cfg.family not in ("audio", "vlm"))
        with jax.default_device(device):
            self.pool = model.init_slot_cache_paged(cfg, s, ecfg.max_cache,
                                                    num_pages, ecfg.page_size)
            self.last = jnp.zeros((s, 1), jnp.int32)
            self.active = jnp.zeros((s,), bool)
            self.frames = (jnp.zeros((s, 1, cfg.frontend_dim), jnp.float32)
                           if cfg.family == "audio" else None)
            self._null_spec = null_spec(s)   # all-greedy lanes, built once
        self.slots: list[Optional[ServeRequest]] = [None] * s
        self.jobs: deque[_PrefillJob] = deque()
        self.pos_host = np.zeros(s, np.int64)      # per-slot next write index
        self.active_host = np.zeros(s, bool)
        # per-slot tokens computed since (re-)admission, seed included — the
        # decode fold_in index. Diverges from len(out_tokens) only while a
        # preempted request replays its recorded history through decode.
        self.emitted = np.zeros(s, np.int64)
        # per-slot sampling lanes (SamplingSpec rows); free slots hold the
        # greedy row (temperature 0), so their garbage lane costs argmax only
        self.samp_keys = np.zeros((s, 2), np.uint32)
        self.samp_temp = np.zeros((s,), np.float32)
        self.samp_topk = np.zeros((s,), np.int32)
        self.samp_topp = np.ones((s,), np.float32)
        self.samp_rep = np.ones((s,), np.float32)   # 1.0 = penalty off
        self.samp_pres = np.zeros((s,), np.float32)
        self.samp_freq = np.zeros((s,), np.float32)
        # per-slot emitted-token counts over the vocab — the penalty state.
        # Rebuilt from zero at (re-)admission and advanced token by token on
        # the host (replay re-walks the identical sequence, so a resumed
        # request's counts at each fold index equal its first run's)
        self.tok_counts = np.zeros((s, cfg.vocab_size), np.int32)
        self._spec_cache = None            # device copy of the samp_* rows
        self.queue: deque[ServeRequest] = deque()
        self.tick = 0
        self.completed: list[ServeRequest] = []
        self.shed: list[ServeRequest] = []    # admission-refused (anergic class)
        self.rejected: list[ServeRequest] = []  # can never fit a slot (submit)
        self.corrupted: list[ServeRequest] = []  # non-finite decode logits
        # refusal high-water marks for stream(): persistent, so refusals that
        # predate the stream are still reported (once) and a second stream()
        # call does not re-report earlier ones
        self._reported_rejected = 0
        self._reported_shed = 0
        self._reported_corrupted = 0
        self.admission = ImmuneAdmission(ecfg) if ecfg.policy == "immune" \
            else None
        self.mid_stream_admissions = 0     # admissions while other slots decode
        self.unsubmitted = 0               # run() arrivals never reached
        self.concurrency_hw = 0            # max simultaneously occupied slots
        self.chunked_prefill_chunks = 0    # chunk lanes landed
        self.prefill_batch_calls = 0       # batched multi-job prefill dispatches
        self.shared_pages_adopted = 0      # prefix-index hits turned refcount++
        self.prefill_positions_skipped = 0  # prompt positions never recomputed
        self.sharable_prompt_pages = 0     # hit-rate denominator (sharable reqs)
        self.preemptions = 0               # slot evictions under page pressure
        self.preempted_rids: set = set()   # distinct requests ever preempted
        self.replayed_tokens = 0           # recorded tokens re-derived by decode
        self.nowrite_adoptions = 0         # full-last-page adoptions (no fork)
        self.prefill_tokens = 0            # prompt positions actually computed
        # self-speculative decode telemetry
        self.spec_ticks = 0                # fused draft+verify ticks run
        self.spec_drafted = 0              # draft tokens proposed (k per lane)
        self.spec_accepted = 0             # draft tokens accepted and emitted
        self.spec_emitted = 0              # tokens emitted by spec ticks
        #                                    (accepted prefix + bonus)
        # slot groups: parents submitted directly to this engine assemble
        # their joint output here; member requests of router-held parents
        # pass through unregistered (the router owns their book)
        self.group_book = groups.GroupBook()
        self.groups_submitted = 0
        self._group_ready: set = set()     # group ids whose shared prompt
        #                                    pages are registered (lane-0
        #                                    prefill landed) — sibling lanes
        #                                    defer admission until then, so
        #                                    the prompt's pages are charged
        #                                    once and adopted n-1 times
        self._admitted_this_tick = 0
        self._decoding_before_admit = False

    # -- queue ---------------------------------------------------------------
    def submit(self, req: ServeRequest):
        """Queue a request. A prompt+decode budget that can never fit a slot is
        *rejected* (recorded, counted against goodput) rather than raised: an
        open-loop server sheds what it cannot serve, it does not crash."""
        if self.admission is not None and not 0 <= req.rclass < \
                self.ecfg.num_classes:
            raise ValueError(f"request {req.rid}: rclass {req.rclass} outside "
                             f"[0, {self.ecfg.num_classes})")
        if req.submit_time < 0:
            # first submission only: a request re-placed on a survivor after a
            # replica crash keeps its original clock, so wall latency (and a
            # wall-clock deadline) spans crash + replay, not just the last leg
            req.submit_time = time.perf_counter()
        if req.params.group_size > 1 and req.group < 0:
            # slot-group parent: expand into member lanes (identical prompt,
            # per-lane seeds) and queue them; the parent itself never holds a
            # slot. Fit checks are per-member and members are identical, so
            # one probe decides the whole group jointly — a group is admitted
            # whole or rejected whole, never half-scheduled.
            members = groups.expand(req)
            probe = members[0]
            need = len(probe.tokens) + self.cfg.frontend_tokens \
                + probe.max_new_tokens
            if need > self.ecfg.max_cache \
                    or self._need_pages(probe) > self.alloc.usable_pages:
                req.finish_reason = "rejected"
                self.rejected.append(req)
                return
            self.groups_submitted += 1
            self.group_book.register(req)
            for m in members:
                m.submit_time = req.submit_time
                self.queue.append(m)
            return
        need = len(req.tokens) + self.cfg.frontend_tokens + req.max_new_tokens
        if need > self.ecfg.max_cache \
                or self._need_pages(req) > self.alloc.usable_pages:
            req.finish_reason = "rejected"  # terminal on the request itself,
            self.rejected.append(req)       # so a journal scan sees the same
            return                          # reason the stream reports
        self.queue.append(req)

    # -- sampling lanes ------------------------------------------------------
    def _pool_spec(self) -> model.SamplingSpec:
        """The slot pool's per-lane sampling rows. Lanes only change at
        admission (``_seed_slot``) and retirement (``_retire``), so the device
        arrays are cached between those events rather than re-uploaded per
        decoded token."""
        if self._spec_cache is None:
            self._spec_cache = model.SamplingSpec(
                keys=jnp.asarray(self.samp_keys),
                temperature=jnp.asarray(self.samp_temp),
                top_k=jnp.asarray(self.samp_topk),
                top_p=jnp.asarray(self.samp_topp),
                rep_penalty=jnp.asarray(self.samp_rep),
                pres_penalty=jnp.asarray(self.samp_pres),
                freq_penalty=jnp.asarray(self.samp_freq))
        return self._spec_cache

    def _seed_slot(self, req: ServeRequest, logits) -> Array:
        """Sample/argmax the request's first token from its prefill logits and
        bind its sampling lane to the slot (capture the logits row if the
        parity oracle asked for it). ``api.spec_for`` builds the batch-of-1
        lane, so the seed-token draw is bitwise the one-shot facade's."""
        self.samp_keys[req.slot] = req.params.key()
        self.samp_temp[req.slot] = req.params.temperature
        self.samp_topk[req.slot] = req.params.top_k
        self.samp_topp[req.slot] = req.params.top_p
        self.samp_rep[req.slot] = req.params.repetition_penalty
        self.samp_pres[req.slot] = req.params.presence_penalty
        self.samp_freq[req.slot] = req.params.frequency_penalty
        self.tok_counts[req.slot] = 0      # penalty counts rebuild from zero
        #                                    (replay re-walks the same tokens)
        self._spec_cache = None
        if self.ecfg.capture_logits and not req.out_tokens:
            req.out_logits.append(pull(logits)[0, -1].copy())
        return _seed_token(logits, spec_for([req.params]),
                           do_sample=not req.params.is_greedy)

    def _emit_seed(self, req: ServeRequest, logits, first) -> None:
        """Record the prefill-seeded first token. A request resuming from
        preemption already holds its history — the seed (bitwise identical by
        the fold-index discipline) is re-derived, not re-recorded."""
        tok = int(pull(first)[0, 0])
        if req.params.has_penalties:
            # the seed draw itself saw zero counts (both backends agree); the
            # seed token is counted from the next draw on — replay included,
            # since the re-derived seed is bitwise the recorded one
            self.tok_counts[req.slot, tok] += 1
        if req.out_tokens:
            self.replayed_tokens += 1
            req.replayed_tokens += 1
            return
        req.out_tokens.append(tok)
        if req.params.logprobs:
            req.out_logprobs.append(
                float(pull(_chosen_lp(logits, first))[0, 0]))
            tv, ti = _topk_lp(logits, req.params.logprobs)
            req.out_topk.append(([int(x) for x in pull(ti)[0]],
                                 [float(x) for x in pull(tv)[0]]))

    # -- paging --------------------------------------------------------------
    def _chunkable(self, req: ServeRequest) -> bool:
        """Chunked prefill only where it is bitwise-exact vs one-shot prefill:
        attention stacks always; MoE only at dropless expert capacity (capacity
        is per-call, so a finite capacity factor can drop different tokens per
        chunking); SSM when the prompt and chunk align to ``ssm_chunk``
        (state-resume preserves the scan's op order); VLM (prefix-LM mask over
        the patch prefix) and RG-LRU hybrids (splitting the associative scan
        regroups the rounding) fall back to one-shot."""
        c = self.ecfg.prefill_chunk
        if not c or self.cfg.family == "vlm" or self.cfg.frontend_tokens:
            return False
        kinds = set(transformer.layer_kinds(self.cfg))
        if "moe" in kinds:
            # dropless iff capacity >= worst-case per-expert load: cf >= E/k
            dropless = self.cfg.capacity_factor * self.cfg.experts_per_token \
                >= self.cfg.num_experts
            if not dropless:
                return False
        if kinds <= {"attn", "moe"}:
            return True
        if kinds == {"ssm"}:
            return len(req.tokens) % c == 0 and c % self.cfg.ssm_chunk == 0
        return False

    def _sharable(self, req: ServeRequest) -> bool:
        """Prefix sharing needs both exactness conditions at once: K/V a pure
        function of the token prefix (no frontend inputs, no recurrent state
        that would be missing the shared positions) and a chunked tail prefill
        to land only the unshared suffix."""
        return self._share_ok and self._chunkable(req)

    def _match(self, req: ServeRequest):
        """Prefix-index match for ``req``, capped so the padded chunk tail
        stays inside ``max_cache``. Returns ``(full_hits, partial, shared_len)``
        — ``shared_len`` prompt positions already resident (never the last
        prompt token: it is always recomputed to seed decoding)."""
        if not self._sharable(req):
            return [], None, 0
        full, partial = self.alloc.match_prefix(req.tokens)
        plen = len(req.tokens)
        c, ps = self.ecfg.prefill_chunk, self.ecfg.page_size

        def padded_end(sl):
            return sl + -(-(plen - sl) // c) * c

        sl = len(full) * ps + (partial[1] if partial else 0)
        while sl and padded_end(sl) > self.ecfg.max_cache:
            if partial is not None:       # degrade: drop the partial page,
                partial = None            # then whole full pages, until the
            else:                         # padded tail fits the block table
                full = full[:-1]
            sl = len(full) * ps
        return full, partial, sl

    def _need_pages(self, req: ServeRequest, shared_len: int = 0) -> int:
        """Worst-case pages this request can ever hold: prompt (+ chunk
        padding of the unshared tail) plus its full decode budget."""
        plen = len(req.tokens) + self.cfg.frontend_tokens
        cover = plen + req.max_new_tokens
        if self._chunkable(req):
            c = self.ecfg.prefill_chunk
            cover = max(cover, shared_len + -(-(plen - shared_len) // c) * c)
        return pages_for(cover, self.ecfg.page_size)

    def _table_row(self, slot: int) -> Array:
        return jnp.asarray(self.alloc.table()[slot])

    # -- admission -----------------------------------------------------------
    def _admit_into(self, req: ServeRequest, slot: int) -> bool:
        """Try to admit ``req`` into ``slot``; False = not enough pages *after*
        prefix-share credit (the caller defers the request). A full-page
        prefix hit — live or pinned — is adopted (refcount++), never charged.

        Under ``admission_mode="reserve"`` the request's worst case (prompt +
        full decode budget) reserves up front; under ``"preempt"`` only its
        *current* footprint (the padded prompt tail) is charged — decode
        growth acquires pages on demand and preempts a lower-priority slot if
        the pool runs dry. A preempted request re-enters here unchanged: it
        re-prefills its original prompt and re-derives its recorded tokens by
        replaying decode (same lane key, same fold indices — bitwise the same
        tokens), because prefill-computed and decode-computed logits are not
        interchangeable bitwise."""
        full, partial, sl = self._match(req)
        plen = len(req.tokens) + self.cfg.frontend_tokens
        c, ps = self.ecfg.prefill_chunk, self.ecfg.page_size
        chunkable = self._chunkable(req)
        # no-write last page: the prompt ends exactly on the shared page's
        # boundary and only its final token is unshared — the single write the
        # tail chunk makes into the shared page (position plen-1) is bitwise
        # what the page already holds (same token prefix, same position), so
        # the page is adopted as-is and the CoW fork is skipped entirely
        nowrite = (partial is not None and chunkable
                   and sl == plen - 1 and plen % ps == 0)
        if self.ecfg.admission_mode == "reserve":
            base = self._need_pages(req, sl)
        else:
            cover = sl + -(-(plen - sl) // c) * c if chunkable else plen
            # a resumed request's footprint is *proven*, not worst-case: replay
            # re-derives every recorded token before any new work, so admit it
            # only once pages for prompt + recorded tokens are actually there —
            # re-entering on the prompt cover alone stalls mid-replay, gets
            # re-evicted, and churns the pool (re-prefilling the prompt each
            # lap) without the tail ever progressing
            cover = max(cover, plen + len(req.out_tokens))
            base = pages_for(cover, ps)
        charge = base - len(full) - (1 if nowrite else 0)
        # adoption of a pinned chain consumes reclaimable capacity the charge
        # would otherwise count on — net the matched pinned pages out first
        matched = full + ([partial[0]] if partial else [])
        avail = self.alloc.available() - self.alloc.pinned_among(matched)
        if charge > min(avail, self.maxp):
            return False
        if full:
            self.alloc.adopt(slot, full, rclass=req.rclass)
        if self.ecfg.admission_mode == "reserve":
            self.alloc.reserve(slot, charge)
        if self._sharable(req):
            self.sharable_prompt_pages += pages_for(plen, ps)
            self.shared_pages_adopted += len(full) + (1 if partial else 0)
            self.prefill_positions_skipped += sl
        req.slot = slot
        if req.admit_tick < 0:
            req.admit_tick = self.tick
            req.admit_time = time.perf_counter()
        if req.preempt_tick >= 0:          # resuming after preemption
            req.requeue_ticks += self.tick - req.preempt_tick
            req.preempt_tick = -1
        self.slots[slot] = req
        if self._decoding_before_admit:
            self.mid_stream_admissions += 1
        self._admitted_this_tick += 1
        if chunkable:
            if partial is not None:
                self.alloc.adopt(slot, [partial[0]], rclass=req.rclass)
                if nowrite:
                    self.nowrite_adoptions += 1
                else:
                    # the unshared tail starts mid-page: CoW-fork the donor's
                    # page (tail prefill writes divergent data into it this
                    # very admission) — the device copy replaces recomputing
                    # the shared positions
                    src, dst = self.alloc.cow_fork(slot, len(full))
                    self.pool = _copy_page(self.pool, jnp.asarray(src),
                                           jnp.asarray(dst), self.cfg)
            total = sl + -(-(plen - sl) // c) * c
            self.jobs.append(_PrefillJob(req=req, slot=slot, p0=sl, total=total,
                                         length=plen,
                                         share=self._sharable(req)))
            return True
        logits, one = _prefill_one(self.params, self.cfg, req.prompts(),
                                   self.ecfg.max_cache, self.router_bias)
        first = self._seed_slot(req, logits)
        self.alloc.ensure(slot, pages_for(plen, ps))
        self.pool, self.last, self.active = _splice(
            self.pool, one, jnp.asarray(slot), self._table_row(slot), first,
            self.last, self.active, self.cfg)
        self.active_host[slot] = True
        self.pos_host[slot] = plen
        self.emitted[slot] = 1
        req.prefill_tokens += plen
        self.prefill_tokens += plen
        self._emit_seed(req, logits, first)
        return True

    # -- preemption ----------------------------------------------------------
    def _victim_score(self, req: ServeRequest) -> tuple:
        """Preemption priority, highest evicted first: anergic classes, then
        classes already over their latency budget, then the highest remembered
        cost, then the *latest arrival* (within an immune-equal group the
        oldest resident is never evicted, so it always runs to completion and
        frees its pages — the classic livelock-free discipline; scoring by
        progress or preemption count instead lets pressure either starve one
        victim or rotate across the whole pool, both of which blow up the
        tail); least progress / rid break remaining ties (FIFO engines score
        on arrival/progress alone), so victim choice is always
        deterministic."""
        over = 1.0 if self._over_budget_now(req) else 0.0
        if self.admission is not None:
            anergy = self.admission.anergy_level(req.rclass)
            cost = self.admission.remembered_cost(req.rclass)
        else:
            anergy = cost = 0.0
        # group-aware: evicting one member cascades to its resident siblings
        # (_preempt), so a member's progress stake is the whole group's —
        # scoring a lane alone would let page pressure evict an n-lane group
        # to reclaim one lane's pages while destroying n lanes of work
        progress = len(req.out_tokens)
        if req.group >= 0:
            progress = sum(len(r.out_tokens) for r in self.slots
                           if r is not None and r.group == req.group)
        return (anergy, over, cost, req.arrival, -progress, req.rid)

    def _pick_victim(self) -> Optional[int]:
        """The occupied slot preemption should evict first (the stalling slot
        itself is a candidate — if it is the lowest-priority resident, it
        self-preempts rather than evicting more deserving work)."""
        best, best_score = None, None
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            score = self._victim_score(req)
            if best_score is None or score > best_score:
                best, best_score = slot, score
        return best

    def _free_slot(self, slot: int) -> None:
        """Return ``slot`` to the pool: drop its request binding, release its
        pages (refcount--; shared and pinnable chains stay resident), zero its
        host-side decode state, and reset its sampling lane to the free-slot
        argmax row."""
        self.slots[slot] = None
        self.pool, self.active = _release(self.pool, self.active,
                                          jnp.asarray(slot), self.cfg)
        self.alloc.release(slot)
        self.active_host[slot] = False
        self.pos_host[slot] = 0
        self.emitted[slot] = 0
        self.samp_temp[slot] = 0.0
        self.samp_topk[slot] = 0
        self.samp_topp[slot] = 1.0
        self.samp_rep[slot] = 1.0
        self.samp_pres[slot] = 0.0
        self.samp_freq[slot] = 0.0
        self.tok_counts[slot] = 0
        self._spec_cache = None

    def _preempt_one(self, slot: int) -> None:
        """Evict ``slot``'s request: drop its pages and any in-flight prefill
        job, and re-queue it at the front for exact re-entry — re-admission
        re-prefills the original prompt and replays its recorded tokens
        through decode, reproducing them bitwise."""
        req = self.slots[slot]
        self.jobs = deque(j for j in self.jobs if j.slot != slot)
        self._free_slot(slot)
        req.slot = -1
        req.preemptions += 1
        req.preempt_tick = self.tick
        self.preemptions += 1
        self.preempted_rids.add(req.rid)
        if req.group >= 0:
            # eviction may drop the shared chain's last refcount — the ready
            # bit is stale until some lane's re-prefill re-registers it.
            # Leaving it set lets every lane re-admit at once, each paying a
            # full un-shared prefill: the group's footprint nearly doubles,
            # runs the pool dry again, and the cascade livelocks.
            self._group_ready.discard(req.group)
        self.queue.appendleft(req)

    def _preempt(self, slot: int) -> None:
        """Evict ``slot``'s request — and, for a slot-group member, its
        resident sibling lanes with it (joint preemption: the group moves
        through the queue as a unit and its shared prefix refcounts drop
        together). Eviction order is descending lane, so the appendleft
        sequence leaves lane 0 at the queue front and the group re-admits in
        lane order — lane 0 re-registers the shared prefix before its
        siblings re-adopt it."""
        req = self.slots[slot]
        targets = [slot]
        if req.group >= 0:
            targets = [s for _, s in sorted(
                ((r.lane, s) for s, r in enumerate(self.slots)
                 if r is not None and r.group == req.group), reverse=True)]
        for s in targets:
            self._preempt_one(s)

    def _acquire(self, slot: int, npages: int) -> bool:
        """Grow ``slot`` to ``npages``, resolving page exhaustion by
        preemption (admission_mode="preempt"). Returns False when the slot's
        own request was the lowest-priority resident and preempted itself —
        the caller must stop driving that slot. Each eviction releases a
        resident's refcounts, so the loop strictly shrinks occupancy and
        terminates; a lone request always fits (submit() rejects anything
        whose worst case exceeds the pool)."""
        if self.ecfg.admission_mode == "reserve":
            self.alloc.ensure(slot, npages)       # covered by the reservation
            return True
        req = self.slots[slot]
        while True:
            # a victim's group cascade may have evicted this slot's own
            # request as a sibling — growing the (now free) slot would charge
            # pages to nobody; the caller must stop driving it
            if req is not None and self.slots[slot] is not req:
                return False
            try:
                self.alloc.ensure(slot, npages)
                return True
            except OutOfPages:
                victim = self._pick_victim()
                if victim is None or victim == slot:
                    self._preempt(slot)
                    return False
                self._preempt(victim)

    def _admit(self):
        self._admitted_this_tick = 0
        # mid-stream means spliced in while another slot was actually decoding
        # — slots filled earlier in this same admission pass don't count
        self._decoding_before_admit = any(r is not None for r in self.slots)
        free = [i for i, r in enumerate(self.slots) if r is None]
        if not free or not self.queue:
            return
        if self.admission is None:                      # FIFO baseline
            while free and self.queue:
                if self._deferred_member(self.queue[0]) \
                        or not self._admit_into(self.queue[0], free[0]):
                    break     # strict FIFO: an unfit head blocks the line
                self.queue.popleft()
                free.pop(0)
            return
        adm = self.admission
        # tolerance turned shedding: requests of anergic classes are rejected
        # outright (not parked — a parked convoy would hold queue pressure high
        # and block the IL-2 revival it is waiting for). Shedding one group
        # member sheds the group: a half-shed group can never finish jointly.
        for req in [r for r in self.queue if not adm.admissible(r.rclass)]:
            if req not in self.queue:
                continue                  # already cancelled with its group
            self.queue.remove(req)
            req.finish_reason = "shed"
            self.shed.append(req)
            req.finish_tick = self.tick
            req.finish_time = time.perf_counter()
            if req.group >= 0:
                self._cancel_group(req.group, "shed")
        if adm.throttled():                             # delayed suppression
            return
        # anticipation: order by *remembered* class cost, not queue position;
        # a candidate the page pool cannot hold yet is skipped (deferred), so
        # a big request waiting for pages never blocks smaller ones — the
        # paged pool's admissive win over fixed rows
        cost = self._predicted_costs()
        candidates = sorted(self.queue,
                            key=lambda r: (cost[r.rclass], r.arrival, r.rid))
        for req in candidates:
            if not free:
                break
            if self._deferred_member(req):
                continue
            if not self._admit_into(req, free[0]):
                continue
            self.queue.remove(req)
            free.pop(0)

    def _deferred_member(self, req: ServeRequest) -> bool:
        """Sibling lanes of a sharable group wait until some member's prompt
        pages are registered in the prefix index (lane 0's prefill landing),
        so they adopt the shared pages (refcount++) instead of each paying a
        full prefill — the group's prompt is charged once. Non-sharable
        configs never defer: there is nothing to adopt.

        The wait is bounded by a *lower lane still pending*: if no sibling
        ahead of this lane is queued or mid-prefill, nobody is ever going to
        register the chain (lane 0 already retired, or its registration was
        evicted with it), so the lane admits now — adopting the chain if it
        survived, paying its own prefill if not. Deferring on the ready bit
        alone would park such a lane forever."""
        if req.group < 0 or req.lane == 0 or not self._sharable(req):
            return False
        if req.group in self._group_ready:
            return False
        return (any(q.group == req.group and q.lane < req.lane
                    for q in self.queue)
                or any(j.req.group == req.group and j.req.lane < req.lane
                       for j in self.jobs))

    def _cancel_group(self, gid: int, reason: str) -> None:
        """Joint retirement on abnormal exit: one member shed or corrupted
        takes its sibling lanes with it — resident lanes release their slots,
        queued lanes leave the queue, all with the member's ``reason`` (the
        stream reports each, and the group book folds them into one abnormal
        parent output). A group either completes whole or fails whole."""
        sink = {"shed": self.shed, "corrupted": self.corrupted,
                "rejected": self.rejected}[reason]
        for slot, r in enumerate(self.slots):
            if r is None or r.group != gid:
                continue
            self.jobs = deque(j for j in self.jobs if j.slot != slot)
            self._free_slot(slot)
            r.slot = -1
            r.finish_reason = reason
            r.finish_tick = self.tick
            r.finish_time = time.perf_counter()
            sink.append(r)
        for r in [q for q in self.queue if q.group == gid]:
            self.queue.remove(r)
            r.finish_reason = reason
            r.finish_tick = self.tick
            r.finish_time = time.perf_counter()
            sink.append(r)
        self._group_ready.discard(gid)

    def _predicted_costs(self) -> np.ndarray:
        """Per-class cost estimate: the EMA memory, floored by what currently
        running requests have already revealed (ticks held so far is a lower
        bound on their class's true cost). Without the reveal, the cold-start
        memory is all zeros and the first burst of heavies convoys the pool."""
        with span(IMMUNE):
            cost = pull(self.admission.memory.value).astype(np.float64)
        for r in self.slots:
            if r is not None:
                cost[r.rclass] = max(cost[r.rclass], self.tick - r.admit_tick)
        return cost

    # -- chunked prefill ------------------------------------------------------
    def _finish_job(self, job: _PrefillJob, logits):
        """Final chunk landed: sample/argmax the first token from its logits,
        activate the slot, and (for sharable prompts) register its full prompt
        pages in the prefix index, so later admissions can adopt them — the
        pages' K/V is now fully resident."""
        first = self._seed_slot(job.req, logits)
        self.pool, self.last, self.active = _activate(
            self.pool, self.last, self.active, jnp.asarray(job.slot),
            first, jnp.asarray(job.length, jnp.int32))
        self.active_host[job.slot] = True
        self.pos_host[job.slot] = job.length
        self.emitted[job.slot] = 1
        self._emit_seed(job.req, logits, first)
        if job.share:
            self.alloc.register_prefix(job.slot, job.req.tokens,
                                       rclass=job.req.rclass)
            if job.req.group >= 0:
                # the group's shared prompt pages are now adoptable: sibling
                # lanes deferred in _admit may enter and refcount++ them
                self._group_ready.add(job.req.group)

    def _prefill_tick(self):
        """Land one chunk of up to ``prefill_streams`` front prefill jobs (one
        batched compiled call on attention stacks; one job per tick
        otherwise). The jobs' slots stay inactive while the other slots
        decode, so long prompts never stall the pool — and with multiple
        streams they no longer serialize behind each other either."""
        if not self.jobs:
            return
        c, page = self.ecfg.prefill_chunk, self.ecfg.page_size
        if self._multi_prefill:
            j = self.ecfg.prefill_streams
            take: list[_PrefillJob] = []
            while self.jobs and len(take) < j:
                job = self.jobs.popleft()
                if not self._acquire(job.slot, pages_for(job.p0 + c, page)):
                    continue          # the job's own request self-preempted
                # that acquire may have preempted a job already taken: keep
                # only lanes whose slot still belongs to their request
                take = [t for t in take if self.slots[t.slot] is t.req]
                take.append(job)
            if not take:
                return
            toks = np.zeros((j, c), np.int32)
            tables = np.zeros((j, self.maxp), np.int32)   # padding lanes: null
            p0s = np.zeros((j,), np.int32)
            last_idxs = np.zeros((j,), np.int32)
            for lane, job in enumerate(take):
                end = job.p0 + c
                seg = job.req.tokens[job.p0:min(end, len(job.req.tokens))]
                toks[lane, :len(seg)] = seg
                p0s[lane] = job.p0
                last_idxs[lane] = min(max(job.length - 1 - job.p0, 0), c - 1)
                job.req.prefill_tokens += len(seg)
                self.prefill_tokens += len(seg)
            tbl = self.alloc.table()          # one snapshot after the acquires
            for lane, job in enumerate(take):
                tables[lane] = tbl[job.slot]
            logits_j, self.pool = _prefill_chunks(
                self.params, self.cfg, {"tokens": jnp.asarray(toks)},
                self.pool, jnp.asarray(tables), jnp.asarray(p0s),
                jnp.asarray(last_idxs), self.router_bias)
            self.chunked_prefill_chunks += len(take)
            self.prefill_batch_calls += 1
            unfinished = []
            for lane, job in enumerate(take):
                job.p0 += c
                if job.p0 >= job.total:
                    self._finish_job(job, logits_j[lane:lane + 1])
                else:
                    unfinished.append(job)
            for job in reversed(unfinished):      # keep front-of-queue order
                self.jobs.appendleft(job)
            return
        job = self.jobs[0]
        end = job.p0 + c
        if not self._acquire(job.slot, pages_for(end, page)):
            return                    # the job's request was requeued
        toks = np.zeros((c,), np.int32)
        seg = job.req.tokens[job.p0:min(end, len(job.req.tokens))]
        toks[:len(seg)] = seg
        job.req.prefill_tokens += len(seg)
        self.prefill_tokens += len(seg)
        chunk = {"tokens": jnp.asarray(toks)[None]}
        if self.cfg.family == "audio":
            fr = np.zeros((c, self.cfg.frontend_dim), np.float32)
            fseg = job.req.frames[job.p0:min(end, len(job.req.frames))]
            fr[:len(fseg)] = fseg
            chunk["frames"] = jnp.asarray(fr)[None]
        last_idx = min(max(job.length - 1 - job.p0, 0), c - 1)
        logits, self.pool = _prefill_chunk(
            self.params, self.cfg, chunk, self.pool, self._table_row(job.slot),
            jnp.asarray(job.p0, jnp.int32), jnp.asarray(last_idx, jnp.int32),
            jnp.asarray(job.slot, jnp.int32), self.router_bias)
        self.chunked_prefill_chunks += 1
        job.p0 = end
        if end >= job.total:
            self.jobs.popleft()
            self._finish_job(job, logits)

    # -- retirement ----------------------------------------------------------
    def _slo(self, req: ServeRequest) -> tuple:
        """``(latency, bar)`` for this completed request's SLO accounting,
        both in ONE unit: a declared ``deadline`` is wall-clock seconds and is
        judged against wall-clock latency; otherwise tick latency is judged
        against the tick-denominated ``EngineConfig.latency_budget``. (The old
        ``_budget`` helper handed a wall-clock deadline to tick comparisons —
        a deadline-bearing request was judged over/under budget in the wrong
        unit.)"""
        if req.deadline is not None and req.wall_latency_s is not None:
            return req.wall_latency_s, float(req.deadline)
        return float(req.latency), float(self.ecfg.latency_budget)

    def _met_budget(self, req: ServeRequest) -> bool:
        """Did this completed request meet its latency bar (its own wall-clock
        deadline if declared, the engine-wide tick budget otherwise)?"""
        lat, bar = self._slo(req)
        return lat <= bar

    def _over_budget_now(self, req: ServeRequest) -> bool:
        """Mid-flight over-budget signal (victim scoring), same unit
        discipline as ``_slo`` but on elapsed time: wall-clock elapsed against
        a declared deadline, tick elapsed against the engine budget."""
        if req.deadline is not None:
            return req.submit_time >= 0 and \
                time.perf_counter() - req.submit_time > req.deadline
        return (self.tick - req.arrival) > self.ecfg.latency_budget

    def _finished(self, req: ServeRequest) -> bool:
        """Per-request retirement: any of the request's stop-token ids ends it
        the tick the token is emitted (the token is kept, like the old
        ``eos_id``); otherwise its own ``max_new_tokens`` budget does. Records
        the ``finish_reason`` the RequestOutput stream reports."""
        p = req.params
        if p.stop and req.out_tokens and req.out_tokens[-1] in p.stop:
            req.finish_reason = "stop"
            return True
        if len(req.out_tokens) >= p.max_new_tokens:
            req.finish_reason = "length"
            return True
        return False

    def _retire(self):
        for slot, req in enumerate(self.slots):
            if req is None or not self.active_host[slot] \
                    or not self._finished(req):
                continue
            req.finish_tick = self.tick
            req.finish_time = time.perf_counter()
            self.completed.append(req)
            self._free_slot(slot)             # incl. unused reservation (stop)
            if self.admission is not None:
                # cost = slot-ticks actually consumed: emitted tokens PLUS any
                # recorded tokens re-derived after preemption — a replayed
                # token burns the same decode tick a fresh one does, and
                # charging emissions alone taught the memory that exactly the
                # preempt-prone classes it should suppress were cheap
                lat, bar = self._slo(req)
                self.admission.observe_completion(
                    req.rclass,
                    cost=float(len(req.out_tokens) + req.replayed_tokens),
                    latency=lat, budget=bar)

    def _retire_corrupted(self, slot: int) -> None:
        """Retire a lane whose decode logits came back non-finite: the
        request terminates with ``finish_reason="corrupted"`` (surfaced via
        the stream, counted against goodput) and the slot's pages return to
        the pool — streaming the garbage token, or letting the poisoned KV
        keep feeding the shared sampling step, would be worse than losing
        the lane. No cost observation: the corruption is a hardware/kernel
        event, not a workload signal the immune memory should learn."""
        req = self.slots[slot]
        req.finish_reason = "corrupted"
        req.finish_tick = self.tick
        req.finish_time = time.perf_counter()
        self.corrupted.append(req)
        self._free_slot(slot)
        if req.group >= 0:
            # joint retirement: the group cannot finish whole anymore
            self._cancel_group(req.group, "corrupted")

    # -- decode ticks --------------------------------------------------------
    def _plain_step(self, do_sample: bool, use_penalties: bool,
                    want_lp: bool, want_k: int) -> None:
        """One sequential decode tick: one token for every active slot."""
        # each lane's fold_in index is its request's emitted-token count
        # since admission (seed included) — identical to the one-shot
        # loop's index, and during post-preemption replay it re-walks
        # 0..n-1 so the re-derived tokens are bitwise the recorded ones
        counts = jnp.asarray(self.emitted, jnp.int32)
        spec = self._pool_spec() if do_sample else self._null_spec
        pen = jnp.asarray(self.tok_counts) if use_penalties else None
        nxt, self.last, self.pool, ok, logits, lps, topk = _decode_tick(
            self.params, self.cfg_decode, self.pool, self.last, self.active,
            jnp.asarray(self.alloc.table()), self.router_bias, self.frames,
            spec, counts, pen, attn_backend=self.ecfg.attn_backend,
            do_sample=do_sample,
            return_logits=self.ecfg.capture_logits,
            return_logprobs=want_lp, use_penalties=use_penalties,
            return_topk=want_k)
        nxt_host = pull(nxt)[:, 0]
        ok_host = pull(ok)
        lg_host = pull(logits)[:, -1] if logits is not None else None
        lp_host = pull(lps)[:, 0] if lps is not None else None
        tv_host, ti_host = (pull(topk[0]), pull(topk[1])) \
            if topk is not None else (None, None)
        bad: list[int] = []
        for slot, req in enumerate(self.slots):
            if req is None or not self.active_host[slot] \
                    or self._finished(req):
                continue
            if not ok_host[slot]:
                bad.append(slot)    # poisoned lane: token is garbage
                continue
            if self.emitted[slot] >= len(req.out_tokens):
                req.out_tokens.append(int(nxt_host[slot]))
                if lg_host is not None:
                    req.out_logits.append(lg_host[slot].copy())
                if lp_host is not None and req.params.logprobs:
                    req.out_logprobs.append(float(lp_host[slot]))
                if tv_host is not None and req.params.logprobs:
                    k = req.params.logprobs
                    req.out_topk.append(
                        ([int(x) for x in ti_host[slot][:k]],
                         [float(x) for x in tv_host[slot][:k]]))
            else:
                self.replayed_tokens += 1   # replaying recorded history
                req.replayed_tokens += 1
            if req.params.has_penalties:
                # the emitted (or bitwise re-derived) token joins the lane's
                # penalty counts for every draw after this one
                self.tok_counts[slot, int(nxt_host[slot])] += 1
            self.emitted[slot] += 1
        self.pos_host[self.active_host] += 1
        for slot in bad:
            self._retire_corrupted(slot)

    def _spec_step(self) -> None:
        """One self-speculative tick: fused draft+verify, then the host-side
        greedy accept loop. Per lane: accept the longest draft prefix where
        ``d_j == argmax(row j-1)`` plus the bonus token ``argmax(row a)``,
        stopping early at the request's stop/budget boundary — every emitted
        token is bitwise the sequential greedy tick's, so preemption replay
        and the parity oracle hold across spec ticks unchanged."""
        k = self.ecfg.spec_decode
        drafts, am, ok, logits, new_pool = specdec.spec_tick(
            self.params, self.cfg_decode, self.pool, self.last, self.active,
            jnp.asarray(self.alloc.table()), k=k,
            depth=self.ecfg.spec_draft_layers,
            attn_backend=self.ecfg.attn_backend,
            return_logits=self.ecfg.capture_logits,
            router_bias=self.router_bias)
        drafts_h = pull(drafts)
        am_h = pull(am)
        ok_h = pull(ok)
        lg_h = pull(logits) if logits is not None else None
        last_h = pull(self.last).copy()       # writable copy
        self.spec_ticks += 1
        bad: list[int] = []
        for slot, req in enumerate(self.slots):
            if req is None or not self.active_host[slot] \
                    or self._finished(req):
                continue
            if not ok_h[slot]:
                bad.append(slot)
                continue
            a = 0
            while a < k and int(drafts_h[slot, a]) == int(am_h[slot, a]):
                a += 1
            self.spec_drafted += k
            emitted_now = 0
            for j in range(a + 1):
                tok = int(drafts_h[slot, j]) if j < a else int(am_h[slot, a])
                if self.emitted[slot] >= len(req.out_tokens):
                    req.out_tokens.append(tok)
                    if lg_h is not None:
                        req.out_logits.append(lg_h[slot, j].copy())
                else:
                    self.replayed_tokens += 1
                    req.replayed_tokens += 1
                self.emitted[slot] += 1
                emitted_now += 1
                last_h[slot, 0] = tok
                if self._finished(req):
                    break               # stop/budget: the rest is never real
            self.spec_accepted += min(emitted_now, a)
            self.spec_emitted += emitted_now
            # pos advances by exactly what was emitted: verify wrote K/V for
            # positions pos..pos+k, of which pos..pos+emitted_now-1 hold
            # precisely what sequential decode would have written; the stale
            # tail is causally masked and overwritten before it is ever read
            self.pos_host[slot] += emitted_now
        self.last = jnp.asarray(last_h)
        self.pool = {"layers": new_pool["layers"],
                     "pos": jnp.asarray(self.pos_host, jnp.int32)}
        for slot in bad:
            self._retire_corrupted(slot)

    # -- one tick ------------------------------------------------------------
    def step(self):
        """One engine tick: admit into free slots, land a prefill chunk, decode
        one token for every active slot, retire finished sequences, advance the
        immune states. Runs on the engine's device, inside a ``STEP`` span
        that holds one span per phase (``serve.spans``)."""
        with jax.default_device(self.device), span(STEP):
            self._tick()

    def _tick(self):
        with span(ADMIT):
            self._admit()
        with span(PREFILL):
            self._prefill_tick()
        self.concurrency_hw = max(self.concurrency_hw,
                                  sum(r is not None for r in self.slots))
        # sample only when a resident request asks to: both do_sample variants
        # of the compiled step stay in jit's cache, so all-greedy stretches
        # run the pure argmax step even after sampled traffic. Penalties ride
        # the sampling lane (greedy-with-penalties is its temperature-0 row).
        use_penalties = any(r is not None and r.params.has_penalties
                            for r in self.slots)
        do_sample = use_penalties or any(
            r is not None and not r.params.is_greedy for r in self.slots)
        want_lp = any(r is not None and r.params.logprobs
                      for r in self.slots)
        want_k = max((r.params.logprobs for r in self.slots
                      if r is not None), default=0)
        # self-speculative tick: only when every resident is greedy with no
        # penalty/logprob state to advance per emitted token — then one fused
        # draft+verify step can emit up to spec_decode+1 tokens per lane,
        # each bitwise what the sequential greedy tick would have emitted
        use_spec = self._spec_ok and not do_sample and not want_lp
        page = self.ecfg.page_size
        lookahead = self.ecfg.spec_decode if use_spec else 0
        with span(PAGES):
            for slot in np.flatnonzero(self.active_host):
                slot = int(slot)
                if not self.active_host[slot]:
                    continue          # preempted by an earlier slot's growth
                # decode writes at pos (a spec tick at pos..pos+k, clamped to
                # the slot's logical capacity — writes past it are dropped
                # and belong to tokens the budget check never emits):
                # append pages lazily at the boundary, preempting the
                # lowest-priority resident if the pool is dry
                cover = min(int(self.pos_host[slot]) + 1 + lookahead,
                            self.ecfg.max_cache)
                self._acquire(slot, pages_for(cover, page))
        if self.active_host.any():
            with span(DECODE):
                if use_spec:
                    self._spec_step()
                else:
                    self._plain_step(do_sample, use_penalties, want_lp,
                                     want_k)
        with span(RETIRE):
            self._retire()
        if self.admission is not None:
            demand = np.zeros(self.ecfg.num_classes, np.float64)
            for r in self.queue:
                demand[r.rclass] += 1.0
            self.admission.end_tick(self._admitted_this_tick, len(self.queue),
                                    demand, self._predicted_costs())
        self.tick += 1

    # -- driver --------------------------------------------------------------
    def _output_for(self, req: ServeRequest, tick: int, new_tokens: list,
                    finished: bool,
                    reason: Optional[str] = None) -> RequestOutput:
        done = finished and reason is None
        new_lp = full_lp = topk = None
        if req.params.logprobs:
            n = len(req.out_tokens)
            new_lp = list(req.out_logprobs[n - len(new_tokens):n])
            full_lp = list(req.out_logprobs)
            topk = list(req.out_topk)
        return RequestOutput(
            rid=req.rid, new_tokens=new_tokens, tokens=list(req.out_tokens),
            finished=finished,
            finish_reason=reason if reason is not None
            else (req.finish_reason if done else None),
            tick=tick, arrival=req.arrival, admit_tick=req.admit_tick,
            finish_tick=req.finish_tick,
            latency_ticks=req.latency if done else None,
            wall_latency_s=req.wall_latency_s if done else None,
            deadline_met=self._met_budget(req) if done else None,
            new_logprobs=new_lp, logprobs=full_lp, top_logprobs=topk,
            preemptions=req.preemptions, requeue_ticks=req.requeue_ticks)

    def stream(self, requests: Optional[list] = None,
               max_ticks: int = 10_000) -> Iterator[RequestOutput]:
        """Open-loop drive as an iterator: submit each request at its
        ``arrival`` tick, step until everything completes (or ``max_ticks``),
        and yield a ``RequestOutput`` per request per tick of progress —
        ``new_tokens`` is the delta since the previous output for that rid,
        and the terminal output carries the finish reason and the
        tick/wall-clock latency accounting. Requests the engine refuses are
        reported too (finish_reason "rejected" / "shed", including refusals
        from ``submit()`` calls made before the stream started), and requests
        still queued or in-flight when the ``max_ticks`` backstop fires get a
        final ``finish_reason="timeout"`` output (``finished=False`` — the
        engine still holds them and can be stepped further), so the stream is
        a complete account of every submission's fate."""
        pending = sorted(requests or [], key=lambda r: (r.arrival, r.rid))
        i = 0
        sent: dict = {}                      # rid -> tokens already yielded
        while True:
            while i < len(pending) and pending[i].arrival <= self.tick:
                self.submit(pending[i])
                i += 1
            # kept current every iteration (not just on drain): a consumer
            # may break out of the stream early, and arrivals never let in
            # must still count as demand in stats() — otherwise a policy that
            # stalls into the backstop flatters its goodput
            self.unsubmitted = len(pending) - i
            t = self.tick
            for req in self.rejected[self._reported_rejected:]:
                out = self._output_for(req, t, [], True, reason="rejected")
                yield out
                done = self.group_book.offer(req, out)
                if done is not None:
                    yield done
            self._reported_rejected = len(self.rejected)
            drained = (i == len(pending) and not self.queue
                       and all(r is None for r in self.slots))
            if drained or t >= max_ticks:
                if not drained:              # backstop: account for the rest
                    live = [r for r in self.slots if r is not None]
                    for req in live + list(self.queue):
                        k = sent.get(req.rid, 0)
                        yield self._output_for(
                            req, t, list(req.out_tokens[k:]), False,
                            reason="timeout")
                break
            ndone = len(self.completed)
            self.step()
            for req in self.shed[self._reported_shed:]:  # anergy refusals
                out = self._output_for(req, t, [], True, reason="shed")
                yield out
                done = self.group_book.offer(req, out)
                if done is not None:
                    yield done
            self._reported_shed = len(self.shed)
            for req in self.corrupted[self._reported_corrupted:]:
                out = self._output_for(req, t, [], True, reason="corrupted")
                yield out
                done = self.group_book.offer(req, out)
                if done is not None:
                    yield done
            self._reported_corrupted = len(self.corrupted)
            live = [r for r in self.slots if r is not None]
            for req in live + self.completed[ndone:]:
                n = len(req.out_tokens)
                k = sent.get(req.rid, 0)
                finished = req.finish_tick == t
                if n == k and not finished:
                    continue
                sent[req.rid] = n
                out = self._output_for(req, t, list(req.out_tokens[k:n]),
                                       finished)
                yield out
                if finished:
                    # group member landed: when it is the group's last lane,
                    # the assembled parent output follows it in the stream
                    done = self.group_book.offer(req, out)
                    if done is not None:
                        yield done

    def run(self, requests: list, max_ticks: int = 10_000) -> dict:
        """Open-loop drive: submit each request at its ``arrival`` tick, run
        until everything completes (or ``max_ticks``); returns ``stats()``.
        ``stream()`` with the outputs discarded."""
        for _ in self.stream(requests, max_ticks=max_ticks):
            pass
        return self.stats()

    def stats(self) -> dict:
        lat = np.asarray([r.latency for r in self.completed], np.float64)
        wall = np.asarray([r.wall_latency_s for r in self.completed
                           if r.wall_latency_s is not None], np.float64) * 1e3
        toks = int(sum(len(r.out_tokens) for r in self.completed))
        # goodput bar is per-request: a request's own wall-clock deadline when
        # declared, the engine-wide tick budget otherwise (unit-consistent)
        in_budget = sum(1 for r in self.completed if self._met_budget(r))
        in_flight = sum(r is not None for r in self.slots)
        # every request the trace produced, wherever it ended up — the goodput
        # denominator, so a policy that stalls into the max_ticks backstop
        # (requests still queued, in-flight, or never submitted) cannot
        # flatter itself by under-counting demand
        demand = (len(self.completed) + len(self.shed) + len(self.rejected)
                  + len(self.corrupted) + len(self.queue) + in_flight
                  + self.unsubmitted)
        # no completions -> the tail is unbounded, not "best ever"
        empty = float("inf")
        return {
            "policy": self.ecfg.policy,
            "ticks": self.tick,
            "completed": len(self.completed),
            "shed": len(self.shed),
            "rejected": len(self.rejected),
            "corrupted": len(self.corrupted),
            "unserved": len(self.queue) + in_flight + self.unsubmitted,
            "tokens": toks,
            "throughput": toks / max(self.tick, 1),
            "p50_latency": float(np.percentile(lat, 50)) if lat.size else empty,
            "p99_latency": float(np.percentile(lat, 99)) if lat.size else empty,
            # fraction of total demand served within the latency budget: shed
            # and rejected requests count against goodput — rejection is not a
            # free lunch
            "goodput": in_budget / max(demand, 1),
            "mid_stream_admissions": self.mid_stream_admissions,
            # paged-memory telemetry: the perf trajectory BENCH_serve.json tracks
            "page_size": self.ecfg.page_size,
            "pages_budget": self.alloc.usable_pages,
            "pages_in_use": self.alloc.pages_in_use,
            "pages_hw": self.alloc.high_water,
            "concurrency_hw": self.concurrency_hw,
            "chunked_prefill_chunks": self.chunked_prefill_chunks,
            "prefill_batch_calls": self.prefill_batch_calls,
            # prefix-sharing telemetry: adopted = refcount++ instead of
            # reserve+prefill; hit rate over the prompt pages of sharable
            # admissions; skipped = prompt positions never re-forwarded
            "attn_backend": self.ecfg.attn_backend,
            "prefix_sharing": bool(self.ecfg.prefix_sharing),
            "shared_pages_adopted": self.shared_pages_adopted,
            "cow_forks": self.alloc.cow_forks,
            "prefill_positions_skipped": self.prefill_positions_skipped,
            "prefix_hit_rate": self.shared_pages_adopted
            / max(self.sharable_prompt_pages, 1),
            "prefill_tokens": self.prefill_tokens,
            "nowrite_adoptions": self.nowrite_adoptions,
            # KV memory hierarchy: pinned prefix cache + preemption telemetry
            "admission_mode": self.ecfg.admission_mode,
            "pin_pages": self.alloc.pin_pages,
            "pages_pinned": self.alloc.pages_pinned,
            "pins": self.alloc.pins,
            "pinned_pages_adopted": self.alloc.pinned_hits,
            "pin_evictions": self.alloc.evictions,
            "pinned_hit_rate": self.alloc.pinned_hits
            / max(self.sharable_prompt_pages, 1),
            "preemptions": self.preemptions,
            "preempted_requests": len(self.preempted_rids),
            "replayed_tokens": self.replayed_tokens,
            # request-facing API telemetry: wall-clock latency over
            # completions (ms) and how much of the traffic asked to sample
            "p99_wall_ms": float(np.percentile(wall, 99)) if wall.size
            else empty,
            "sampled_requests": sum(1 for r in self.completed
                                    if not r.params.is_greedy),
            "deadline_requests": sum(1 for r in self.completed
                                     if r.deadline is not None),
            # self-speculative decoding: accept rate over proposed drafts and
            # how much of the emitted stream came out of fused spec ticks
            "spec_decode": self.ecfg.spec_decode,
            "spec_ticks": self.spec_ticks,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_emitted": self.spec_emitted,
            "spec_accept_rate": self.spec_accepted / max(self.spec_drafted, 1),
            # slot groups
            "groups_submitted": self.groups_submitted,
            "group_members_completed": sum(1 for r in self.completed
                                           if r.group >= 0),
            "penalized_requests": sum(1 for r in self.completed
                                      if r.params.has_penalties),
        }

    # -- placement telemetry (read by serve.router for global placement) -----
    def class_costs(self) -> np.ndarray:
        """Per-class remembered decode cost (the ``ImmuneMemory`` slot-tick
        EMA) — the router's load model. All zeros under the FIFO policy,
        which has no memory."""
        if self.admission is None:
            return np.zeros(self.ecfg.num_classes, np.float64)
        return np.asarray(self.admission.memory.value, np.float64)

    def anergy_levels(self) -> np.ndarray:
        """Per-class anergy levels. A router drains a replica for classes it
        holds anergic (no new placements until IL-2 revives them) — placing
        there would only have local admission shed the request."""
        if self.admission is None:
            return np.zeros(self.ecfg.num_classes, np.float64)
        return np.asarray(self.admission.anergy.level, np.float64)

    def prefix_affinity(self, req: ServeRequest) -> int:
        """Prompt positions of ``req`` already resident in this engine's page
        pool (live shared or pinned chains). Placement affinity: routing the
        request here skips exactly this much prefill."""
        return self._match(req)[2]

    def pinned_chain_keys(self) -> list:
        """Token-content keys of this engine's pinned prefix-cache pages."""
        return self.alloc.pinned_chain_keys()

    def occupancy(self) -> int:
        """Queued + resident (incl. mid-prefill) requests — the classic
        join-shortest-queue load signal, memory-free by design."""
        return len(self.queue) + sum(r is not None for r in self.slots)

    def evacuate(self) -> list:
        """Strip every in-flight and queued request for re-placement on
        another replica (crash recovery — the fleet router calls this when it
        declares this replica dead). Only host-side request objects survive:
        recorded ``out_tokens`` plus the original prompt are exactly what
        re-admission elsewhere needs for bitwise-exact recovery (re-prefill
        the proven prompt, replay the recorded tokens through decode — the
        preemption machinery, pointed at a different replica). The device
        state is abandoned; the caller must fence this engine (never step it
        again). Returns residents in slot order, then the queue in order —
        deterministic, so re-placement is reproducible."""
        lost = []
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            req.slot = -1
            lost.append(req)
        lost.extend(self.queue)
        self.queue.clear()
        self.jobs.clear()
        self.slots = [None] * self.ecfg.num_slots
        self.active_host[:] = False
        self.pos_host[:] = 0
        self.emitted[:] = 0
        for slot in range(self.ecfg.num_slots):
            self.alloc.release(slot)      # keep the (dead) books consistent
        return lost

    # -- durability: warm-state snapshot export / import ---------------------
    def export_warm_state(self) -> tuple[dict, list]:
        """Snapshot this engine's *learned* state: the indexed prefix forest
        (pinned cache entries and live prompt chains alike — immutable once
        registered; token keys + the pages' actual K/V, gathered from the
        device pool) and the immune memories (per-class cost EMAs, anergy,
        regulator, pin-value EMAs). Returns ``(meta, kv)`` — a JSON-able dict plus the
        host K/V arrays, page-major then leaf-major, ``meta["kv_per_page"]``
        arrays per page. In-flight request state is deliberately NOT here:
        the write-ahead journal owns requests; the snapshot owns what was
        *learned* from them. Reads device state but never mutates it, so a
        snapshot cadence never stalls decode."""
        forest = self.alloc.export_pinned()
        kv: list[np.ndarray] = []
        per = 0
        for e in forest:
            page = e.pop("page")
            arrs = self._gather_page_kv(page)
            per = len(arrs)
            kv.extend(arrs)
        meta = {
            "forest": forest,
            "kv_per_page": per,
            "pin_memory": self.alloc.pin_memory_state().tolist(),
            "admission": (self.admission.export_state()
                          if self.admission is not None else None),
        }
        return meta, kv

    def import_warm_state(self, meta: dict, kv: list) -> int:
        """Rebuild the warm state exported by :meth:`export_warm_state` into
        this (fresh) engine: pinned chains re-index under newly allocated
        pages, their saved K/V scatters back into the device pool (zero
        recompute — a returning tenant adopts them exactly as before the
        power loss), and the immune memories resume their EMAs. Returns the
        number of pinned pages restored."""
        if meta.get("pin_memory") is not None:
            self.alloc.set_pin_memory_state(meta["pin_memory"])
        if self.admission is not None and meta.get("admission"):
            self.admission.import_state(meta["admission"])
        placed = self.alloc.import_pinned(meta.get("forest") or [])
        per = int(meta.get("kv_per_page") or 0)
        if not placed or not per:
            return len(placed)
        pages = jnp.asarray([p for _, p in placed])
        stacks = [np.stack([kv[i * per + j] for i, _ in placed], axis=1)
                  for j in range(per)]           # (reps, n, page, Hkv, D)
        lane = iter(range(per))

        def scatter(kind, leaf):
            if kind in ("attn", "moe"):
                # (reps, n, page, Hkv, D) -> (reps, Hkv, n, page*D) rows
                return {n: leaf[n].at[:, :, pages].set(jnp.asarray(
                            np.moveaxis(a, 3, 1).reshape(*leaf[n].shape[:2],
                                                         len(placed), -1)))
                        for n, a in (("k", stacks[next(lane)]),
                                     ("v", stacks[next(lane)]))}
            return leaf

        self.pool = {"layers": transformer.map_block_caches(
            self.cfg, scatter, self.pool["layers"]), "pos": self.pool["pos"]}
        return len(placed)

    def _gather_page_kv(self, page: int) -> list:
        """Host copies of one physical page's K/V across every paged layer
        (k then v per layer, segment order), each (reps, page, Hkv, D) — the
        snapshot payload for one pinned page."""
        out: list[np.ndarray] = []

        def gather(kind, leaf):
            if kind in ("attn", "moe"):
                for n in ("k", "v"):
                    rows = leaf[n][:, :, page:page + 1]
                    out.append(np.asarray(layers.page_view(rows,
                                                           self.cfg))[:, 0])
            return leaf

        transformer.map_block_caches(self.cfg, gather, self.pool["layers"])
        return out
