"""Continuous-batching engine: decode-parity oracle + admission behavior.

The correctness anchor is *token parity*: a request served by the engine —
prefilled into an arbitrary slot mid-stream (one-shot or chunk by chunk into
its block-table pages), decoded alongside unrelated sequences at other depths,
retired, its pages freed and reused — must emit exactly the tokens that
one-shot ``serve.decode.generate`` produces for the same prompt and params.
That pins page scatter/gather, per-slot positions (rope + causal masks),
chunked-prefill state threading, and cross-slot isolation in one observable.

MoE runs at the *default* capacity factor on purpose: the engine's decode tick
bumps capacity to be dropless (a garbage lane from an empty slot must never
displace a real request's token at an expert's capacity limit), and one-shot
prefill is a batch-of-1 call identical to the oracle's — so parity must hold
with no capacity pinning at all. The *chunked* MoE case pins capacity to
dropless on both sides instead: expert capacity is per-call, so a chunked
prefill at finite capacity could legitimately drop tokens the one-shot oracle
keeps — parity there is only defined dropless.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import model
from repro.serve import decode, traces
from repro.serve import engine as eng_mod
from repro.serve.api import SamplingParams, ServeRequest

jax.config.update("jax_platform_name", "cpu")


def _smoke_cfg(arch):
    return configs.get_config(arch).smoke()


def _params(cfg):
    return model.init_params(jax.random.PRNGKey(0), cfg)


def _bias(cfg):
    return (jnp.zeros((cfg.num_layers, cfg.num_experts))
            if cfg.num_experts else None)


def _make_requests(cfg, n, seed=0, prompt_lens=(6, 10), steps=(5, 8),
                   stagger=1):
    """Staggered heterogeneous requests; two prompt-length buckets bound the
    number of prefill shapes the engine compiles."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = prompt_lens[rid % len(prompt_lens)]
        req = ServeRequest(
            rid=rid,
            tokens=rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32),
            params=SamplingParams(max_new_tokens=steps[rid % len(steps)]),
            rclass=rid % 2,
            arrival=rid * stagger)
        reqs.append(traces.attach_modality_inputs(req, cfg, rng))
    return reqs


def _oracle_tokens(params, cfg, req, max_cache, bias):
    # req.prompts() is exactly what the engine prefills — same arrays, no copy
    toks, _ = decode.generate(params, cfg, req.prompts(), max_cache=max_cache,
                              steps=req.max_new_tokens, router_bias=bias)
    return [int(t) for t in np.asarray(toks[0])]


class TestDecodeParity:
    """Engine output == one-shot generate, token for token, per family."""

    def test_dense_staggered_trace_token_identical(self):
        """The acceptance trace: >= 8 staggered requests through 3 slots, so
        slots are reused and every admission is mid-stream."""
        cfg = _smoke_cfg("smollm-360m")
        params = _params(cfg)
        ecfg = eng_mod.EngineConfig(num_slots=3, max_cache=48, policy="immune",
                                    num_classes=2, latency_budget=64.0)
        reqs = _make_requests(cfg, 9)
        eng = eng_mod.Engine(params, cfg, ecfg)
        stats = eng.run(reqs, max_ticks=500)
        assert stats["completed"] == 9 and stats["shed"] == 0
        # admissions actually interleaved with other slots' decodes
        assert stats["mid_stream_admissions"] >= 6
        # slots were reused (9 requests > 3 slots) and drained clean: no live
        # pages, every page back on the free list, positions reset
        assert all(r is None for r in eng.slots)
        assert not bool(eng.active.any())
        assert np.asarray(eng.pool["pos"]).tolist() == [0, 0, 0]
        assert stats["pages_in_use"] == 0
        assert 0 < stats["pages_hw"] <= stats["pages_budget"]
        for req in eng.completed:
            oracle = _oracle_tokens(params, cfg, req, ecfg.max_cache, None)
            assert req.out_tokens == oracle, f"request {req.rid} diverged"

    def test_chunked_prefill_mid_stream_token_identical(self):
        """Long prompts land chunk by chunk (one per tick) while other slots
        keep decoding — and the tokens still match one-shot ``generate``
        exactly. The 24-token prompts take 3 chunks each, so every multi-chunk
        prefill overlaps live decodes."""
        cfg = _smoke_cfg("smollm-360m")
        params = _params(cfg)
        ecfg = eng_mod.EngineConfig(num_slots=3, max_cache=48, policy="immune",
                                    num_classes=2, latency_budget=64.0,
                                    prefill_chunk=8)
        reqs = _make_requests(cfg, 8, prompt_lens=(24, 10))
        eng = eng_mod.Engine(params, cfg, ecfg)
        stats = eng.run(reqs, max_ticks=500)
        assert stats["completed"] == 8 and stats["shed"] == 0
        # 4 long prompts x 3 chunks + 4 short x 2 chunks = 20 chunk calls
        assert stats["chunked_prefill_chunks"] == 20
        assert stats["mid_stream_admissions"] >= 5
        for req in eng.completed:
            oracle = _oracle_tokens(params, cfg, req, ecfg.max_cache, None)
            assert req.out_tokens == oracle, \
                f"request {req.rid} diverged after chunked prefill"

    def test_chunked_prefill_moe_dropless_token_identical(self):
        """Chunked MoE prefill at *dropless* capacity (pinned on both engine
        and oracle: capacity is per-call, so finite-capacity drops are not
        comparable across chunkings)."""
        cfg = dataclasses.replace(_smoke_cfg("granite-moe-3b-a800m"),
                                  capacity_factor=8.0)
        params = _params(cfg)
        bias = _bias(cfg)
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=48, policy="fifo",
                                    prefill_chunk=8)
        reqs = _make_requests(cfg, 4, seed=1, prompt_lens=(16, 8), steps=(4, 6))
        eng = eng_mod.Engine(params, cfg, ecfg, router_bias=bias)
        stats = eng.run(reqs, max_ticks=300)
        assert stats["completed"] == 4
        assert stats["chunked_prefill_chunks"] == 6
        for req in eng.completed:
            oracle = _oracle_tokens(params, cfg, req, ecfg.max_cache, bias)
            assert req.out_tokens == oracle, f"moe request {req.rid} diverged"

    def test_recurrent_chunked_prefill_token_identical(self):
        """Position-free recurrent config (mamba2): chunked prefill resumes the
        SSD recurrence + conv tail across chunks; aligned lengths make it
        bitwise-identical to the one-shot oracle."""
        cfg = _smoke_cfg("mamba2-130m")
        params = _params(cfg)
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=48, policy="fifo",
                                    prefill_chunk=8)
        reqs = _make_requests(cfg, 4, seed=1, prompt_lens=(16, 8), steps=(4, 6))
        eng = eng_mod.Engine(params, cfg, ecfg)
        stats = eng.run(reqs, max_ticks=300)
        assert stats["completed"] == 4
        assert stats["chunked_prefill_chunks"] == 6
        assert stats["mid_stream_admissions"] >= 1
        for req in eng.completed:
            oracle = _oracle_tokens(params, cfg, req, ecfg.max_cache, None)
            assert req.out_tokens == oracle, f"ssm request {req.rid} diverged"

    @pytest.mark.parametrize("arch", ["granite-moe-3b-a800m", "paligemma-3b",
                                      "musicgen-medium"])
    def test_moe_vlm_audio_token_identical(self, arch):
        cfg = _smoke_cfg(arch)
        params = _params(cfg)
        bias = _bias(cfg)
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=48, policy="fifo")
        reqs = _make_requests(cfg, 4, seed=1, steps=(4, 6))
        eng = eng_mod.Engine(params, cfg, ecfg, router_bias=bias)
        stats = eng.run(reqs, max_ticks=300)
        assert stats["completed"] == 4
        assert stats["mid_stream_admissions"] >= 1
        for req in eng.completed:
            oracle = _oracle_tokens(params, cfg, req, ecfg.max_cache, bias)
            assert req.out_tokens == oracle, f"{arch} request {req.rid} diverged"


def _shared_prefix_family(cfg, seed=0):
    """A crafted shared-prefix request family: a 48-token donor, a follower
    whose prompt is a strict prefix of it (full-page hits + a partial-page hit
    that must CoW-fork), a same-prompt twin, and two requests behind a second
    prefix — every sharing path in one trace."""
    rng = np.random.default_rng(seed)
    donor = rng.integers(0, cfg.vocab_size, size=48).astype(np.int32)
    other = rng.integers(0, cfg.vocab_size, size=32).astype(np.int32)
    def mk(rid, tokens, max_new_tokens, arrival):
        return ServeRequest(rid=rid, tokens=tokens, arrival=arrival,
                            params=SamplingParams(max_new_tokens=max_new_tokens))
    return [
        mk(rid=0, tokens=donor.copy(), max_new_tokens=12, arrival=0),
        # donor[:40]: 2 full-page hits + partial (page 2, 7 tokens) -> CoW
        mk(rid=1, tokens=donor[:40].copy(), max_new_tokens=6, arrival=8),
        # identical prompt: 2 full-page hits + partial (page 2, 15) -> CoW
        mk(rid=2, tokens=donor.copy(), max_new_tokens=5, arrival=10),
        mk(rid=3, tokens=np.concatenate([other, rng.integers(
            0, cfg.vocab_size, size=6).astype(np.int32)]),
           max_new_tokens=6, arrival=12),
        mk(rid=4, tokens=np.concatenate([other, rng.integers(
            0, cfg.vocab_size, size=9).astype(np.int32)]),
           max_new_tokens=5, arrival=20),
    ]


class TestPrefixSharing:
    """Refcounted prefix sharing: adopted pages and CoW forks must be invisible
    in the tokens (bitwise the one-shot oracle's) and visible in the stats."""

    def test_shared_prefix_admission_token_identical(self):
        """System-prompt traffic through sharing + batched prefill streams:
        full-page hits skip their prefill entirely, and every request still
        emits exactly the one-shot oracle's tokens."""
        cfg = _smoke_cfg("smollm-360m")
        params = _params(cfg)
        ecfg = eng_mod.EngineConfig(num_slots=4, max_cache=64, policy="fifo",
                                    prefill_chunk=8, prefill_streams=2)
        reqs = traces.shared_prefix_trace(cfg, num_requests=10,
                                           num_prefixes=2, prefix_len=32,
                                           suffix_lens=(4, 8),
                                           decode_lens=(6, 10),
                                           arrival_every=2)
        eng = eng_mod.Engine(params, cfg, ecfg)
        stats = eng.run(reqs, max_ticks=500)
        assert stats["completed"] == 10
        # the sharing actually happened: followers adopted the two full
        # prefix pages instead of re-prefilling 32 positions each
        assert stats["shared_pages_adopted"] >= 8
        assert stats["prefill_positions_skipped"] >= 100
        assert stats["prefix_hit_rate"] > 0
        assert stats["prefill_batch_calls"] > 0
        # drained clean: refcounts back to zero, all pages on the free list
        assert stats["pages_in_use"] == 0 and eng.alloc.live_refs() == 0
        for req in eng.completed:
            oracle = _oracle_tokens(params, cfg, req, ecfg.max_cache, None)
            assert req.out_tokens == oracle, \
                f"request {req.rid} diverged over shared pages"

    def test_cow_fork_partial_page_token_identical(self):
        """Partial-page hits adopt the donor's page and CoW-fork it before the
        tail prefill writes — the copy replaces recomputing the shared
        positions, and the tokens stay bitwise the oracle's."""
        cfg = _smoke_cfg("smollm-360m")
        params = _params(cfg)
        ecfg = eng_mod.EngineConfig(num_slots=3, max_cache=64, policy="fifo",
                                    prefill_chunk=8)
        reqs = _shared_prefix_family(cfg)
        eng = eng_mod.Engine(params, cfg, ecfg)
        stats = eng.run(reqs, max_ticks=300)
        assert stats["completed"] == 5
        assert stats["cow_forks"] >= 1          # rid 1 (tail starts mid-page)
        # rid 2's prompt ends exactly on the shared page boundary: adopted
        # with NO fork — its one write into the page is bitwise a no-op
        assert stats["nowrite_adoptions"] >= 1
        assert stats["shared_pages_adopted"] >= 6
        # rid 1 (40-token prompt, 39 positions shared) lands in ONE tail chunk
        # instead of 5 — the O(unique tokens) prefill claim, measurably
        assert stats["chunked_prefill_chunks"] <= 6 + 1 + 1 + 5 + 2
        assert stats["pages_in_use"] == 0 and eng.alloc.live_refs() == 0
        for req in eng.completed:
            oracle = _oracle_tokens(params, cfg, req, ecfg.max_cache, None)
            assert req.out_tokens == oracle, \
                f"request {req.rid} diverged over CoW-forked pages"

    def test_sharing_admits_beyond_free_pool(self):
        """The accounting fix, end to end: at a page budget that worst-case
        fits ONE request, a prefix-twin admits concurrently because it only
        charges its unshared pages — and with sharing off it must wait."""
        cfg = _smoke_cfg("smollm-360m")
        params = _params(cfg)
        rng = np.random.default_rng(0)
        prefix = rng.integers(0, cfg.vocab_size, size=32).astype(np.int32)

        def reqs():
            return [ServeRequest(
                rid=i, tokens=np.concatenate([prefix, rng.integers(
                    0, cfg.vocab_size, size=4).astype(np.int32)]),
                params=SamplingParams(max_new_tokens=6),
                arrival=(0, 8)[i]) for i in range(2)]

        stats = {}
        for share in (True, False):
            # each request worst-cases 3 pages; 4 usable pages total
            ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=64,
                                        policy="fifo", prefill_chunk=8,
                                        num_pages=5, prefix_sharing=share)
            eng = eng_mod.Engine(params, cfg, ecfg)
            stats[share] = eng.run(reqs(), max_ticks=200)
            assert stats[share]["completed"] == 2
            for req in eng.completed:
                oracle = _oracle_tokens(params, cfg, req, ecfg.max_cache, None)
                assert req.out_tokens == oracle
        assert stats[True]["concurrency_hw"] == 2, \
            "prefix-hot twin was spuriously deferred despite full-page hits"
        assert stats[False]["concurrency_hw"] == 1, \
            "share-off engine admitted past its page budget"
        assert stats[True]["pages_hw"] <= 4


class TestPallasBackend:
    """attn_backend='pallas_interpret' runs the kernels.paged_attention
    scalar-prefetch kernel on the live decode path; tokens must match the XLA
    gather fallback exactly — including slots decoding over shared and
    CoW-forked pages — across GQA and MHA head layouts."""

    @pytest.mark.parametrize("kv_heads", [2, 4])  # GQA (4/2) and MHA (4/4)
    def test_engine_decode_token_identical_vs_xla(self, kv_heads):
        cfg = dataclasses.replace(_smoke_cfg("smollm-360m"),
                                  num_kv_heads=kv_heads)
        params = _params(cfg)
        outs = {}
        for backend in ("xla", "pallas_interpret"):
            ecfg = eng_mod.EngineConfig(num_slots=3, max_cache=64,
                                        policy="fifo", prefill_chunk=8,
                                        attn_backend=backend)
            eng = eng_mod.Engine(params, cfg, ecfg)
            stats = eng.run(_shared_prefix_family(cfg), max_ticks=300)
            assert stats["completed"] == 5
            assert stats["cow_forks"] >= 1       # decode covered forked pages
            assert stats["nowrite_adoptions"] >= 1   # and no-write-shared ones
            outs[backend] = {r.rid: r.out_tokens for r in eng.completed}
            for req in eng.completed:
                oracle = _oracle_tokens(params, cfg, req, ecfg.max_cache, None)
                assert req.out_tokens == oracle, \
                    f"[{backend}] request {req.rid} diverged from the oracle"
        assert outs["pallas_interpret"] == outs["xla"]

    def test_compiled_backend_refuses_off_tpu(self):
        """attn_backend='pallas' compiles the kernel: off a TPU the first
        decode tick raises; it never falls back to the interpreter."""
        cfg = _smoke_cfg("smollm-360m")
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=32,
                                    prefill_chunk=8, attn_backend="pallas")
        eng = eng_mod.Engine(_params(cfg), cfg, ecfg)
        with pytest.raises(ValueError, match="interpret mode"):
            eng.run(_make_requests(cfg, 1), max_ticks=20)


class TestEngineMechanics:
    @pytest.fixture(scope="class")
    def dense(self):
        cfg = _smoke_cfg("smollm-360m")
        return cfg, _params(cfg)

    def test_eos_early_stop(self, dense):
        cfg, params = dense
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=48, policy="fifo")
        [probe] = _make_requests(cfg, 1, steps=(6,))
        eng = eng_mod.Engine(params, cfg, ecfg)
        eng.run([probe], max_ticks=50)
        assert len(probe.out_tokens) == 6
        # rerun with a stop id = the 3rd emitted token: output must stop
        # right there, with the per-request finish reason recorded
        [again] = _make_requests(cfg, 1, steps=(6,))
        again.params = SamplingParams(max_new_tokens=6,
                                      stop=(probe.out_tokens[2],))
        eng2 = eng_mod.Engine(params, cfg, ecfg)
        eng2.run([again], max_ticks=50)
        assert again.out_tokens == probe.out_tokens[:3]
        assert again.finish_reason == "stop"
        assert probe.finish_reason == "length"

    def test_single_token_request_retires_at_admission_tick(self, dense):
        cfg, params = dense
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=48, policy="fifo")
        [req] = _make_requests(cfg, 1, steps=(1,))
        eng = eng_mod.Engine(params, cfg, ecfg)
        stats = eng.run([req], max_ticks=20)
        assert stats["completed"] == 1
        assert len(req.out_tokens) == 1
        assert req.finish_tick == req.admit_tick

    def test_submit_rejects_oversized_request_without_raising(self, dense):
        """A prompt+decode budget that can never fit a slot is shed at submit —
        recorded and counted against goodput — not raised mid-stream: an
        open-loop server drops what it cannot serve, it does not crash."""
        cfg, params = dense
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=16)
        eng = eng_mod.Engine(params, cfg, ecfg)
        big, ok = _make_requests(cfg, 2, prompt_lens=(12, 6), steps=(8, 4))
        eng.submit(big)                       # 12 + 8 = 20 > 16: rejected
        eng.submit(ok)                        # 6 + 4 = 10: queued
        assert eng.rejected == [big] and list(eng.queue) == [ok]
        stats = eng.run([], max_ticks=50)     # drain the queued request
        assert stats["completed"] == 1 and stats["rejected"] == 1
        assert big.out_tokens == []
        # the rejected request still counts as demand in goodput
        assert stats["goodput"] <= 0.5

    def test_out_of_pages_backpressure_defers_then_serves(self, dense):
        """Under worst-case reservation, page exhaustion is backpressure, not
        an error: with pages for only one request in flight, the second waits
        in the queue until the first retires, then completes. Nothing is
        dropped, slots never share pages."""
        cfg, params = dense
        # a pool with fewer pages than one slot's worth: a request that fits
        # max_cache but needs more pages than the whole pool has is rejected at
        # submit (it could never be admitted), not left camping in the queue
        tiny = eng_mod.EngineConfig(num_slots=2, max_cache=32, page_size=16,
                                    num_pages=2, policy="fifo",
                                    admission_mode="reserve")  # 1 usable page
        tiny_eng = eng_mod.Engine(params, cfg, tiny)
        [two_pager] = _make_requests(cfg, 1, prompt_lens=(10,), steps=(8,))
        tiny_eng.submit(two_pager)            # needs 2 pages, pool has 1
        assert tiny_eng.rejected == [two_pager] and not tiny_eng.queue

        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=32, page_size=16,
                                    num_pages=3, policy="fifo",
                                    admission_mode="reserve")  # 2 usable pages
        eng = eng_mod.Engine(params, cfg, ecfg)
        reqs = _make_requests(cfg, 2, prompt_lens=(10,), steps=(8,))
        stats = eng.run(reqs, max_ticks=100)  # each request needs 2 pages
        assert stats["completed"] == 2 and stats["rejected"] == 0
        assert stats["concurrency_hw"] == 1, \
            "page budget for one request admitted two at once"
        assert stats["pages_hw"] <= 2
        r0, r1 = sorted(eng.completed, key=lambda r: r.rid)
        assert r1.admit_tick >= r0.finish_tick, \
            "second request admitted before the first released its pages"


class TestImmuneAdmission:
    """Unit-level behavior of the admission controller (no model involved)."""

    def _ecfg(self, **kw):
        base = dict(num_slots=4, max_cache=64, policy="immune", num_classes=3,
                    latency_budget=10.0)
        base.update(kw)
        return eng_mod.EngineConfig(**base)

    def test_burst_throttles_then_recovers(self):
        adm = eng_mod.ImmuneAdmission(self._ecfg())
        none = np.zeros(3)
        assert not adm.throttled()            # fast path: bursts admit freely
        for _ in range(4):                    # sustained full-pool admission
            adm.end_tick(admitted=4, queue_len=10, queued_demand=none,
                         predicted_cost=none)
        assert adm.throttled(), "delayed suppression never engaged"
        for _ in range(60):                   # quiet: suppressor drains response
            adm.end_tick(admitted=0, queue_len=0, queued_demand=none,
                         predicted_cost=none)
        assert not adm.throttled(), "throttle never released"

    def test_blown_budget_sheds_then_pressure_drop_revives(self):
        adm = eng_mod.ImmuneAdmission(self._ecfg())
        demand = np.asarray([1.0, 0.0, 1.0])
        cost = np.asarray([2.0, 2.0, 50.0])   # class 2 cannot meet the budget
        for _ in range(6):                    # high pressure: no IL-2
            adm.observe_completion(0, cost=2.0, latency=3.0)
            adm.end_tick(admitted=1, queue_len=20, queued_demand=demand,
                         predicted_cost=cost)
        assert not adm.admissible(2), "abusive class never shed"
        assert adm.admissible(0) and adm.admissible(1), \
            "healthy classes shed alongside the abusive one"
        for _ in range(20):                   # pressure drops: IL-2 revives
            adm.end_tick(admitted=0, queue_len=0, queued_demand=np.zeros(3),
                         predicted_cost=cost)
        assert adm.admissible(2), "anergy is supposed to be reversible"

    def test_memory_tracks_per_class_cost(self):
        adm = eng_mod.ImmuneAdmission(self._ecfg())
        for _ in range(30):
            adm.observe_completion(0, cost=4.0, latency=5.0)
            adm.observe_completion(1, cost=40.0, latency=45.0)
        assert abs(adm.remembered_cost(0) - 4.0) < 0.5
        assert abs(adm.remembered_cost(1) - 40.0) < 5.0
        assert adm.remembered_cost(2) == 0.0  # untouched class unchanged


class TestImmuneVsFifo:
    def test_immune_tail_no_worse_than_fifo_under_bursts(self):
        """The benchmark's acceptance property, in-suite: bursty heterogeneous
        traffic, identical trace, immune p99 <= FIFO p99 (and goodput at least
        as high) — the anticipation + shedding loop protecting the tail."""
        cfg = _smoke_cfg("smollm-360m")
        params = _params(cfg)
        stats = {}
        for policy in ("fifo", "immune"):
            ecfg = eng_mod.EngineConfig(num_slots=4, max_cache=64,
                                        policy=policy, num_classes=3,
                                        latency_budget=24.0)
            trace = traces.synthetic_trace(cfg, num_requests=24, seed=0)
            eng = eng_mod.Engine(params, cfg, ecfg)
            stats[policy] = eng.run(trace, max_ticks=1200)
        assert stats["fifo"]["completed"] == 24
        imm, fifo = stats["immune"], stats["fifo"]
        assert imm["p99_latency"] <= fifo["p99_latency"], (imm, fifo)
        assert imm["goodput"] >= fifo["goodput"], (imm, fifo)


class TestPreemption:
    """admission_mode="preempt" (the default): admission charges only the
    current footprint, decode-time page exhaustion evicts the lowest-priority
    resident, and an evicted request resumes by replay — re-prefilling its
    original prompt and re-deriving its recorded tokens bitwise."""

    @pytest.fixture(scope="class")
    def dense(self):
        cfg = _smoke_cfg("smollm-360m")
        return cfg, _params(cfg)

    def test_decode_stall_preempts_and_resumes_token_identical(self, dense):
        """Two requests, pages for one worst case: both admit on their prompt
        footprint, decode growth exhausts the pool, the later arrival (least
        progress) is evicted, re-queued, and finishes token-identical to the
        one-shot oracle — including its chosen-token logprobs."""
        cfg, params = dense
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=32, page_size=16,
                                    num_pages=3, policy="fifo")  # 2 usable
        reqs = _make_requests(cfg, 2, prompt_lens=(10,), steps=(8,))
        for r in reqs:
            r.params = dataclasses.replace(r.params, logprobs=True)
        eng = eng_mod.Engine(params, cfg, ecfg)
        stats = eng.run(reqs, max_ticks=200)
        assert stats["completed"] == 2 and stats["rejected"] == 0
        assert stats["concurrency_hw"] == 2, \
            "preempt-mode admission should fill both slots on prompt pages"
        assert stats["preemptions"] >= 1 and stats["preempted_requests"] >= 1
        assert stats["replayed_tokens"] >= 1, \
            "a resumed request re-derives recorded tokens by replay"
        r0, r1 = sorted(eng.completed, key=lambda r: r.rid)
        # deterministic victim: same progress, later arrival, higher rid
        assert r0.preemptions == 0 and r1.preemptions >= 1
        assert r1.requeue_ticks >= 1
        for req in eng.completed:
            probe = ServeRequest(rid=req.rid, tokens=req.tokens,
                                 params=req.params)
            toks, _, lp = decode.generate(params, cfg, probe.prompts(),
                                          max_cache=ecfg.max_cache,
                                          steps=req.max_new_tokens,
                                          return_logprobs=True)
            assert req.out_tokens == [int(t) for t in np.asarray(toks[0])], \
                f"request {req.rid} diverged across preemption"
            assert len(req.out_logprobs) == len(req.out_tokens)
            np.testing.assert_allclose(
                req.out_logprobs,
                np.asarray(lp[0])[:len(req.out_tokens)], atol=1e-5)

    def test_preempt_admits_strictly_deeper_than_reserve(self, dense):
        """The tentpole A/B on one trace and one page budget: worst-case
        reservation serializes the pair, preemptive admission overlaps them —
        strictly deeper concurrency, everything still completes."""
        cfg, params = dense
        depth = {}
        for mode in ("reserve", "preempt"):
            ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=32,
                                        page_size=16, num_pages=3,
                                        policy="fifo", admission_mode=mode)
            eng = eng_mod.Engine(params, cfg, ecfg)
            stats = eng.run(_make_requests(cfg, 2, prompt_lens=(10,),
                                           steps=(8,)), max_ticks=200)
            assert stats["completed"] == 2, mode
            depth[mode] = stats["concurrency_hw"]
        assert depth["preempt"] > depth["reserve"], depth

    def test_victim_score_prefers_anergic_then_over_budget(self, dense):
        """Victim ordering is the immune priority inverted: anergic classes
        first, then over-budget, then highest remembered cost; FIFO tiebreak
        by latest arrival then least progress (oldest resident never
        evicted)."""
        cfg, params = dense
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=32, page_size=16,
                                    policy="immune", num_classes=3,
                                    latency_budget=10.0)
        eng = eng_mod.Engine(params, cfg, ecfg)
        for _ in range(30):   # class 2 becomes the expensive class
            eng.admission.observe_completion(2, cost=40.0, latency=45.0)
        mk = lambda rid, rc, arr: ServeRequest(
            rid=rid, tokens=np.arange(6, dtype=np.int32), rclass=rc,
            arrival=arr)
        cheap, dear = mk(0, 0, 0), mk(1, 2, 0)
        assert eng._victim_score(dear) > eng._victim_score(cheap), \
            "higher remembered class cost should be evicted first"
        eng.tick = 20         # cheap is now over the 10-tick budget
        late = mk(2, 0, 15)
        assert eng._victim_score(cheap) > eng._victim_score(late), \
            "over-budget resident outranks an in-budget one"
        # progress shields: a request with tokens already emitted is kept
        late2 = mk(3, 0, 15)
        late2.out_tokens = [1, 2, 3]
        assert eng._victim_score(late) > eng._victim_score(late2)


class TestPinnedPrefixCache:
    """pin_pages > 0: full-page prefix chains survive refcount zero inside the
    pin budget and returning tenants adopt them instead of re-prefilling."""

    @pytest.fixture(scope="class")
    def dense(self):
        cfg = _smoke_cfg("smollm-360m")
        return cfg, _params(cfg)

    def _trace(self, cfg):
        return traces.returning_tenant_trace(
            cfg, tenants=2, prefix_len=48, suffix_lens=(4,), burst_size=3,
            bursts=2, gap=100, decode_lens=(6,), seed=0)

    def test_returning_tenant_adopts_pinned_pages(self, dense):
        """Pin-on vs pin-off at the same page budget: the second burst adopts
        each tenant's pinned prefix chain (3 pages per tenant) and prefills
        only suffixes — strictly fewer prompt positions computed — and every
        request, pinned-adopt or not, stays token-identical to the oracle."""
        cfg, params = dense
        runs = {}
        for pin in (0, 8):
            ecfg = eng_mod.EngineConfig(num_slots=3, max_cache=64,
                                        page_size=16, prefill_chunk=8,
                                        policy="fifo", num_classes=2,
                                        pin_pages=pin)
            eng = eng_mod.Engine(params, cfg, ecfg)
            stats = eng.run(self._trace(cfg), max_ticks=600)
            assert stats["completed"] == 12, f"pin={pin}"
            for req in eng.completed:
                oracle = _oracle_tokens(params, cfg, req, ecfg.max_cache, None)
                assert req.out_tokens == oracle, \
                    f"request {req.rid} diverged (pin={pin})"
            runs[pin] = stats
        assert runs[0]["pins"] == 0 and runs[0]["pages_pinned"] == 0
        assert runs[0]["pages_in_use"] == 0          # legacy free-on-zero
        assert runs[8]["pins"] >= 6                  # 2 tenants x 3 pages
        assert runs[8]["pinned_pages_adopted"] >= 6  # burst 2 hits the cache
        assert runs[8]["pinned_hit_rate"] > 0
        # drained: every resident page is a pinned cache entry, nothing leaked
        assert runs[8]["pages_in_use"] == runs[8]["pages_pinned"] > 0
        assert runs[8]["prefill_tokens"] < runs[0]["prefill_tokens"], \
            "pinning should cut prompt positions actually computed"

    def test_pin_budget_zero_without_sharing_is_legacy(self, dense):
        """No sharing -> no index -> nothing pinnable: the allocator forces
        pin_pages to 0 and the run behaves exactly like the old allocator."""
        cfg, params = dense
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=64, page_size=16,
                                    prefill_chunk=8, policy="fifo",
                                    num_classes=2, pin_pages=8,
                                    prefix_sharing=False)
        eng = eng_mod.Engine(params, cfg, ecfg)
        stats = eng.run(self._trace(cfg), max_ticks=600)
        assert stats["completed"] == 12
        assert stats["pin_pages"] == 0 and stats["pins"] == 0
        assert stats["pages_in_use"] == 0


class TestImmuneCostAccounting:
    """The immune cost memory must charge what a request actually held: a
    preempted-then-resumed request burns slot-ticks re-deriving its recorded
    tokens, and charging emissions alone would teach the memory that exactly
    the preempt-prone classes it should suppress were cheap."""

    @pytest.fixture(scope="class")
    def dense(self):
        cfg = _smoke_cfg("smollm-360m")
        return cfg, _params(cfg)

    def test_replayed_request_charges_more_than_unpreempted(self, dense):
        """Identical request pair through a tiny pool (forces preemption of
        the later arrival) vs an ample one (no preemption): the preempted
        class's EMA must come out strictly higher — replayed slot-ticks are
        charged — while the untouched class's EMA is identical."""
        cfg, params = dense
        runs = {}
        for name, num_pages in (("tiny", 3), ("ample", None)):
            ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=32,
                                        page_size=16, num_pages=num_pages,
                                        policy="immune", num_classes=2,
                                        latency_budget=64.0)
            eng = eng_mod.Engine(params, cfg, ecfg)
            stats = eng.run(_make_requests(cfg, 2, prompt_lens=(10,),
                                           steps=(8,)), max_ticks=200)
            assert stats["completed"] == 2 and stats["shed"] == 0, name
            runs[name] = eng
        tiny, ample = runs["tiny"], runs["ample"]
        r0t, r1t = sorted(tiny.completed, key=lambda r: r.rid)
        r0a, r1a = sorted(ample.completed, key=lambda r: r.rid)
        # the tiny pool preempted the later arrival (class 1) and it replayed
        assert r1t.preemptions >= 1 and r1t.replayed_tokens >= 1
        assert r0t.replayed_tokens == 0 and r1a.replayed_tokens == 0
        # both runs emitted the same tokens; only the replay differs
        assert r1t.out_tokens == r1a.out_tokens
        # class 1's remembered cost reflects the replayed slot-ticks ...
        assert tiny.admission.remembered_cost(1) > \
            ample.admission.remembered_cost(1)
        # ... and the unpreempted class is charged identically in both runs
        assert tiny.admission.remembered_cost(0) == \
            pytest.approx(ample.admission.remembered_cost(0))


class TestBudgetUnits:
    """One unit per comparison: a declared ``deadline`` is wall-clock seconds
    judged against wall-clock latency; the engine-wide ``latency_budget`` is
    ticks judged against tick latency. The old ``_budget`` helper handed the
    wall-clock deadline to tick comparisons."""

    @pytest.fixture(scope="class")
    def dense(self):
        cfg = _smoke_cfg("smollm-360m")
        return cfg, _params(cfg)

    def test_deadline_generous_in_ticks_tight_in_wall_clock(self, dense):
        """A 50-second deadline on a ~9-tick request: the old code compared
        ticks (9 <= 50 -> met) no matter how slow the wall clock was. Judged
        in the deadline's own unit, a (simulated) 60 s wall latency misses and
        a 1 s one meets — tick latency must not leak into the comparison."""
        cfg, params = dense
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=32, policy="fifo")
        reqs = _make_requests(cfg, 1, prompt_lens=(6,), steps=(8,))
        reqs[0].deadline = 50.0
        eng = eng_mod.Engine(params, cfg, ecfg)
        stats = eng.run(reqs, max_ticks=100)
        assert stats["completed"] == 1 and stats["deadline_requests"] == 1
        req = eng.completed[0]
        assert req.latency <= 50, "sanity: generous measured in ticks"
        # simulate the wall clock (real timing would flake under compile):
        # 60 s > the 50 s deadline -> missed, regardless of tick latency
        req.finish_time = req.submit_time + 60.0
        assert eng._met_budget(req) is False
        assert eng.stats()["goodput"] == 0.0
        # 1 s < 50 s -> met
        req.finish_time = req.submit_time + 1.0
        assert eng._met_budget(req) is True
        assert eng.stats()["goodput"] == 1.0

    def test_no_deadline_judged_in_ticks(self, dense):
        """Without a declared deadline the bar is the tick-denominated engine
        budget against tick latency — wall clock never enters."""
        cfg, params = dense
        ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=32, policy="fifo",
                                    latency_budget=5.0)
        eng = eng_mod.Engine(params, cfg, ecfg)
        stats = eng.run(_make_requests(cfg, 1, prompt_lens=(6,), steps=(8,)),
                        max_ticks=100)
        assert stats["completed"] == 1
        req = eng.completed[0]
        assert req.latency > 5, "sanity: blows the 5-tick budget"
        assert eng._met_budget(req) is False
        # wall clock (microseconds here) must not rescue a tick-budget miss
        lat, bar = eng._slo(req)
        assert (lat, bar) == (float(req.latency), 5.0)
