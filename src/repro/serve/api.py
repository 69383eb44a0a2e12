"""Unified serving API: the request-facing types both serving backends speak.

The engine (``serve.engine.Engine``) and one-shot decode (``serve.decode``)
are two backends of one front door:

  * ``SamplingParams`` — per-request decoding intent: temperature / top-p /
    top-k / seed, token budget (``max_new_tokens``), stop-token ids.
    ``temperature == 0`` is *exact* greedy — bitwise the argmax path.
  * ``ServeRequest``   — prompt + params + the scheduling metadata the immune
    admission loop reads (``rclass``, ``arrival``, optional per-request
    wall-clock ``deadline`` overriding the engine-wide tick budget). This is
    the
    anticipation argument (Boulmier et al., PAPERS.md) made concrete: the
    scheduler sees each request's declared intent, not just its queue slot.
  * ``RequestOutput``  — incremental token deltas plus finish reason and
    per-request tick/wall-clock latency accounting. ``Engine.stream()``
    yields one per request per tick of progress; the one-shot ``generate``
    facade returns one finished output per request.

Sampling itself lives in ``models.model.sample_tokens`` (per-lane masked
top-k/top-p over the logits lane, per-lane PRNG keys folded with the lane's
emitted-token count) so the engine's single compiled decode step and the
one-shot decode loop run the *same* lane math — seeded sampling is then
token-identical engine-vs-oneshot, and the parity oracle can compare raw
logits bitwise below the sampler.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models import model
from . import decode

Array = jax.Array

GREEDY_TEMPERATURE = 0.0


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding parameters. Frozen: shared freely across requests.

    ``temperature == 0`` selects the exact greedy path (bitwise argmax);
    ``top_k == 0`` and ``top_p == 1.0`` disable their filters. ``seed`` fixes
    the request's PRNG key stream, so a seeded request emits identical tokens
    on every run and on either backend. ``stop`` token ids retire the request
    the tick one is emitted (the stop token is included in the output, like
    the old ``eos_id``)."""

    temperature: float = GREEDY_TEMPERATURE
    top_p: float = 1.0
    top_k: int = 0
    seed: int = 0
    max_new_tokens: int = 16
    stop: tuple = ()
    logprobs: int = 0          # k: record each chosen token's logprob (under
    #                            the raw model distribution, before
    #                            temperature) plus the top-k alternative
    #                            logprobs per position; 0 disables. Accepts
    #                            the legacy bool spelling (True == 1).
    repetition_penalty: float = 1.0   # >1 discourages reuse; 1.0 disabled
    presence_penalty: float = 0.0     # flat once-seen penalty; 0.0 disabled
    frequency_penalty: float = 0.0    # per-occurrence penalty; 0.0 disabled
    n: int = 1                 # completions to return (slot-group lanes)
    best_of: int = 0           # 0: off; >= n: sample best_of lanes, keep the
    #                            n best by cumulative chosen-token logprob

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if not 0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if self.repetition_penalty <= 0:
            raise ValueError(
                f"repetition_penalty must be > 0, got {self.repetition_penalty}")
        if self.n < 1:
            raise ValueError(f"n must be >= 1, got {self.n}")
        if self.best_of and self.best_of < self.n:
            raise ValueError(
                f"best_of must be 0 or >= n, got {self.best_of} < {self.n}")
        object.__setattr__(self, "logprobs", int(self.logprobs))
        if self.logprobs < 0:
            raise ValueError(f"logprobs must be >= 0, got {self.logprobs}")
        object.__setattr__(self, "stop", tuple(int(t) for t in self.stop))

    @property
    def is_greedy(self) -> bool:
        return self.temperature == GREEDY_TEMPERATURE

    @property
    def has_penalties(self) -> bool:
        return (self.repetition_penalty != 1.0 or self.presence_penalty != 0.0
                or self.frequency_penalty != 0.0)

    @property
    def group_size(self) -> int:
        """Engine lanes this request owns (``best_of`` supersedes ``n``)."""
        return max(self.n, self.best_of, 1)

    def key(self) -> np.ndarray:
        """Host copy of the request's base PRNG key (2,) uint32."""
        return np.asarray(jax.random.PRNGKey(self.seed))


def spec_for(params_list: Sequence[SamplingParams]) -> model.SamplingSpec:
    """Stack per-request ``SamplingParams`` into the per-lane arrays the
    compiled decode steps consume."""
    return model.SamplingSpec(
        keys=jnp.asarray(np.stack([p.key() for p in params_list])),
        temperature=jnp.asarray([p.temperature for p in params_list],
                                jnp.float32),
        top_k=jnp.asarray([p.top_k for p in params_list], jnp.int32),
        top_p=jnp.asarray([p.top_p for p in params_list], jnp.float32),
        rep_penalty=jnp.asarray([p.repetition_penalty for p in params_list],
                                jnp.float32),
        pres_penalty=jnp.asarray([p.presence_penalty for p in params_list],
                                 jnp.float32),
        freq_penalty=jnp.asarray([p.frequency_penalty for p in params_list],
                                 jnp.float32))


@dataclass
class ServeRequest:
    """One serving request: prompt + sampling params + scheduling metadata.

    ``rclass`` buckets requests into the classes the immune admission
    controller remembers (endpoint, tenant, prompt-shape bucket); ``arrival``
    is the tick the request enters the queue; ``deadline`` is **wall-clock
    seconds after submission** and overrides the engine-wide (tick-denominated)
    latency budget for this request's goodput/anergy accounting when set —
    each bar is only ever compared against a latency in its own unit (see
    ``EngineConfig`` and ``Engine._slo``)."""

    rid: int
    tokens: np.ndarray                     # (L,) int32 prompt
    params: SamplingParams = SamplingParams()
    rclass: int = 0
    arrival: int = 0
    deadline: Optional[float] = None
    patches: Optional[np.ndarray] = None   # vlm prefix embeddings (P, Fd)
    frames: Optional[np.ndarray] = None    # audio frame embeddings (L, Fd)

    # slot-group membership (serve.groups): a parent with params.n/best_of > 1
    # is expanded into group_size member lanes sharing its prompt pages.
    # group == -1: standalone request. Members carry the parent rid in
    # ``group`` and their lane index in ``lane``.
    group: int = -1
    lane: int = 0
    group_size: int = 1

    # filled in by the serving backend
    out_tokens: list = field(default_factory=list)
    out_logits: list = field(default_factory=list)  # per-token (V,) fp32 rows
    #                                                 (capture_logits only)
    out_logprobs: list = field(default_factory=list)  # per-token chosen-token
    #                                                   logprob (params.logprobs)
    out_topk: list = field(default_factory=list)  # per-token (ids, logprobs)
    #                                               top-k alternative pairs
    #                                               (params.logprobs == k)
    finish_reason: Optional[str] = None    # "stop" | "length" | "rejected" |
    #                                        "shed" | "failed" | "corrupted"
    admit_tick: int = -1
    finish_tick: int = -1
    slot: int = -1
    submit_time: float = -1.0              # wall clock, perf_counter seconds
    admit_time: float = -1.0               # wall clock of the first seating
    #                                        (kept across preemption)
    finish_time: float = -1.0
    preemptions: int = 0                   # times evicted from a slot mid-flight
    retries: int = 0                       # times re-placed on a survivor after
    #                                        a replica death (router failover);
    #                                        past the router's retry budget the
    #                                        request terminates with
    #                                        finish_reason="failed"
    replayed_tokens: int = 0               # recorded tokens re-derived by decode
    #                                        after preemption — slot-ticks the
    #                                        request burned beyond its emissions
    requeue_ticks: int = 0                 # ticks spent re-queued after eviction
    preempt_tick: int = -1                 # last eviction tick (-1: not evicted
    #                                        or already re-admitted)
    prefill_tokens: int = 0                # prompt positions actually computed
    #                                        (prefix hits and replays excluded)

    @property
    def max_new_tokens(self) -> int:
        return self.params.max_new_tokens

    @property
    def latency(self) -> int:
        return self.finish_tick - self.arrival

    @property
    def wall_latency_s(self) -> Optional[float]:
        if self.submit_time < 0 or self.finish_time < 0:
            return None
        return self.finish_time - self.submit_time

    def prompts(self) -> dict:
        """The prefill batch-of-1 for this request — the single source of truth
        for what a backend feeds the model (the parity oracle reuses it)."""
        p = {"tokens": jnp.asarray(self.tokens, jnp.int32)[None]}
        if self.patches is not None:
            p["patches"] = jnp.asarray(self.patches)[None]
        if self.frames is not None:
            p["frames"] = jnp.asarray(self.frames)[None]
        return p


@dataclass
class RequestOutput:
    """One increment of a request's progress.

    ``Engine.stream()`` yields one per request per tick it gained tokens or
    changed state; ``new_tokens`` is the delta since the previous output for
    the same ``rid`` and ``tokens`` the full stream so far. Terminal outputs
    set ``finished`` with a ``finish_reason`` ("stop" | "length" on normal
    retirement, "rejected" | "shed" when admission refused the request,
    "failed" when a fleet router exhausted the request's crash-retry budget —
    see ``serve.router`` — and "corrupted" when the silent-corruption guard
    caught non-finite decode logits on the request's lane and retired it
    rather than stream garbage) and
    the latency accounting — ``latency_ticks`` in engine ticks,
    ``wall_latency_s`` in wall-clock seconds, ``deadline_met`` against the
    request's own deadline (or the engine budget). A request still queued or
    in-flight when the engine's ``max_ticks`` backstop fires gets a final
    ``finish_reason="timeout"`` output with ``finished=False`` — the engine
    still holds it and can be stepped further."""

    rid: int
    new_tokens: list
    tokens: list
    finished: bool
    finish_reason: Optional[str]
    tick: int
    arrival: int = 0
    admit_tick: int = -1
    finish_tick: int = -1
    latency_ticks: Optional[int] = None
    wall_latency_s: Optional[float] = None
    deadline_met: Optional[bool] = None
    # chosen-token logprobs (None unless SamplingParams.logprobs): the delta
    # aligned 1:1 with new_tokens, and the full stream aligned with tokens
    new_logprobs: Optional[list] = None
    logprobs: Optional[list] = None
    # top-k alternative logprobs (None unless SamplingParams.logprobs == k):
    # per emitted position an (ids, logprobs) pair of the k highest-probability
    # vocab entries under the raw model distribution, aligned with tokens
    top_logprobs: Optional[list] = None
    # slot-group assembly (None unless the request had params.n/best_of > 1):
    # the parent's view of its member lanes — finished member outputs in rank
    # order (cumulative chosen-token logprob when best_of, lane order for n)
    group_outputs: Optional[list] = None
    # preemption accounting: how often this request was evicted mid-flight
    # and how many ticks it spent re-queued waiting for re-admission
    preemptions: int = 0
    requeue_ticks: int = 0


def _finish_oneshot(req: ServeRequest, stream: list, t0: float) -> RequestOutput:
    """Trim a one-shot token stream at the first stop token (inclusive,
    mirroring the engine's retirement) and fill the request/output records."""
    cut, reason = len(stream), "length"
    for i, t in enumerate(stream):
        if t in req.params.stop:
            cut, reason = i + 1, "stop"
            break
    req.out_tokens = stream[:cut]
    req.finish_reason = reason
    req.finish_time = time.perf_counter()
    if req.submit_time < 0:
        req.submit_time = t0
    return RequestOutput(
        rid=req.rid, new_tokens=list(req.out_tokens),
        tokens=list(req.out_tokens), finished=True, finish_reason=reason,
        tick=len(req.out_tokens), arrival=req.arrival, admit_tick=0,
        finish_tick=len(req.out_tokens),
        latency_ticks=len(req.out_tokens),
        wall_latency_s=req.finish_time - req.submit_time)


def generate(params, cfg: ModelConfig,
             requests: Union[ServeRequest, Sequence[ServeRequest]],
             max_cache: int, router_bias: Optional[Array] = None,
             capture_logits: bool = False
             ) -> Union[RequestOutput, Sequence[RequestOutput]]:
    """One-shot serving facade: prefill + decode each request batch-of-1 under
    its own ``SamplingParams``, returning finished ``RequestOutput``s.

    This is the oracle backend: the engine must emit exactly these tokens for
    the same request (greedy bitwise; seeded sampling token-identical), and
    with ``capture_logits`` each request's per-token logits rows land in
    ``req.out_logits`` for the bitwise logits-parity comparison."""
    from . import groups
    single = isinstance(requests, ServeRequest)
    reqs = [requests] if single else list(requests)
    outs = []
    for req in reqs:
        if req.params.group_size > 1:
            t0 = time.perf_counter()
            members = groups.expand(req)
            member_outs = [_oneshot_one(params, cfg, m, max_cache,
                                        router_bias, capture_logits)
                           for m in members]
            outs.append(groups.assemble(req, members, member_outs, t0))
        else:
            outs.append(_oneshot_one(params, cfg, req, max_cache,
                                     router_bias, capture_logits))
    return outs[0] if single else outs


def _oneshot_one(params, cfg: ModelConfig, req: ServeRequest, max_cache: int,
                 router_bias: Optional[Array], capture_logits: bool
                 ) -> RequestOutput:
    """Run one request batch-of-1 through the one-shot decode loop."""
    t0 = time.perf_counter()
    sp = req.params
    sampling = None if (sp.is_greedy and not sp.has_penalties) \
        else spec_for([sp])
    res = decode.generate(params, cfg, req.prompts(), max_cache=max_cache,
                          steps=sp.max_new_tokens, router_bias=router_bias,
                          sampling=sampling, return_logits=capture_logits,
                          return_logprobs=bool(sp.logprobs),
                          use_penalties=sp.has_penalties,
                          return_topk=sp.logprobs)
    stream = [int(t) for t in np.asarray(res[0][0])]
    out = _finish_oneshot(req, stream, t0)
    idx = 2
    if capture_logits:
        lg = np.asarray(res[idx][0])                       # (steps, V) fp32
        idx += 1
        req.out_logits = [lg[i].copy()
                          for i in range(len(req.out_tokens))]
    if sp.logprobs:
        lp = np.asarray(res[idx][0])                       # (steps,) fp32
        idx += 1
        req.out_logprobs = [float(lp[i])
                            for i in range(len(req.out_tokens))]
        out.new_logprobs = list(req.out_logprobs)
        out.logprobs = list(req.out_logprobs)
        tv, ti = res[idx]
        tv, ti = np.asarray(tv[0]), np.asarray(ti[0])      # (steps, k)
        req.out_topk = [([int(t) for t in ti[i]], [float(v) for v in tv[i]])
                        for i in range(len(req.out_tokens))]
        out.top_logprobs = list(req.out_topk)
    return out
