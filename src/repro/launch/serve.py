"""Serving entry point: batched prefill + decode on one device, optionally
restoring trained parameters from a checkpoint directory.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --batch 4 --steps 32 [--restore /tmp/run1]

``--stream`` switches from the one-shot fixed batch to the continuous-batching
engine driven by a synthetic open-loop arrival trace (bursty, heterogeneous
request classes — or ``--trace shared-prefix`` for system-prompt traffic that
exercises refcounted prefix page sharing), with admission governed by the
immune primitives. The engine is driven through ``Engine.stream()``: per-token
``RequestOutput`` deltas print as they are emitted (first ``--show-stream``
request ids), and ``--temperature/--top-p/--top-k/--sample-seed`` give every
request a seeded sampling lane instead of greedy:

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --stream --requests 40 --slots 4 [--policy fifo] \
        [--temperature 0.8 --top-p 0.9 --sample-seed 7] \
        [--trace shared-prefix|returning-tenant|contention|fleet] \
        [--no-prefix-sharing] [--pin-pages 8] [--admission reserve] \
        [--logprobs] [--attn-backend pallas_interpret] [--prefill-streams 2]

``--replicas N`` (with ``--stream``) serves the trace through the
multi-replica placement router instead of one engine: N identical replicas
(each on its own device when the host has at least N, else all on the
default device), one global queue, per-tick placement under
``--router immune|rr|jsq`` —
immune placement routes by prefix affinity, drains anergic replicas, and
prices backlog at remembered per-class cost (see serve/router.py):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --stream --trace fleet --replicas 3 --router immune --pin-pages 8

``--faults "crash@8:r1 rejoin@24:r1"`` (with ``--replicas > 1``) scripts
seeded, tick-exact replica faults into the run (``serve.faults`` grammar:
crash / slow / stall / page-pressure / cold rejoin) and exercises the
router's missed-deadline health machine — suspect fencing, bitwise-exact
evacuation + re-placement on survivors, retry budget, rejoin rewarming.
``--trace fleet-faults`` serves the fleet trace with a crash+rejoin plan
auto-sized to the arrival window when ``--faults`` is not given:

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --stream --trace fleet-faults --replicas 3 --router immune \
        [--faults "crash@7:r1 rejoin@17:r1"]

``--journal PATH`` arms the write-ahead request journal (and, with
``--snapshot-dir``/``--snapshot-every``, warm snapshots of the pinned cache
+ immune memories) on the router. A fault plan containing ``poweroff@tick``
— or ``--trace fleet-poweroff``, which auto-sizes one to the arrival window
— switches the drive to ``serve.durability.run_durable``: the whole fleet
fail-stops mid-trace, the journal is truncated to its fsync'd prefix, and a
fresh fleet recovers and finishes the trace with bitwise-identical streams:

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
        --stream --trace fleet-poweroff --replicas 2 --router immune \
        --journal /tmp/serve.wal --snapshot-dir /tmp/serve-snap \
        --snapshot-every 4 [--sync-every 2]
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.dist import checkpoint as ckpt
from repro.models import model as model_lib
from repro.serve import decode as serve_mod

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def build_parser() -> argparse.ArgumentParser:
    """The serving CLI's arguments — also the source of ``chip_smoke.py``'s
    engine defaults, so the two cannot drift."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(configs.ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--restore", default=None,
                    help="checkpoint dir from repro.launch.train")
    ap.add_argument("--stream", action="store_true",
                    help="continuous-batching engine on a synthetic open-loop "
                         "arrival trace instead of a one-shot fixed batch")
    ap.add_argument("--policy", default="immune", choices=("immune", "fifo"))
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--latency-budget", type=float, default=24.0)
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged-KV page size (tokens per physical page)")
    ap.add_argument("--pages", type=int, default=None,
                    help="page-pool size incl. the null page; default fully "
                         "provisioned (slots x max_cache worth)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="chunked prefill size; 0 = one-shot prefill")
    ap.add_argument("--prefill-streams", type=int, default=1,
                    help=">1: batch that many concurrent prefill jobs into "
                         "one compiled call per tick (attention stacks)")
    ap.add_argument("--prefix-sharing", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="refcounted prompt-prefix page sharing (CoW forks); "
                         "--no-prefix-sharing for the single-owner allocator")
    ap.add_argument("--attn-backend", default="xla",
                    choices=("xla", "pallas", "pallas_interpret"),
                    help="paged decode attention: XLA gather fallback, or the "
                         "kernels.paged_attention Pallas kernel (pallas = "
                         "compiled on TPU, pallas_interpret = runs anywhere)")
    ap.add_argument("--trace", default="bursty",
                    choices=("bursty", "shared-prefix", "returning-tenant",
                             "contention", "agentic", "fleet", "fleet-faults",
                             "fleet-poweroff"),
                    help="synthetic arrival trace: bursty heterogeneous, "
                         "system-prompt traffic (exercises prefix sharing), "
                         "returning-tenant bursts with drain gaps (exercises "
                         "the pinned prefix cache), page-pool contention "
                         "(exercises preemptive admission), agentic "
                         "multi-turn re-submission with grown prompt "
                         "prefixes (exercises prefix sharing + speculative "
                         "decoding), multi-tenant fleet traffic with "
                         "hot-replica skew (exercises the placement router), "
                         "the fleet trace fault-laced with an auto-sized "
                         "crash+rejoin plan (exercises failover; needs "
                         "--replicas > 1), or the fleet trace with an "
                         "auto-sized full-fleet poweroff + restart "
                         "(exercises journal + snapshot recovery)")
    ap.add_argument("--replicas", type=int, default=1,
                    help=">1: serve through the multi-replica placement "
                         "router (serve.router) — N engine replicas, one "
                         "global queue, per-tick placement")
    ap.add_argument("--faults", default=None, metavar="PLAN",
                    help="script seeded tick-exact replica faults into a "
                         "--replicas > 1 run, e.g. 'crash@8:r1 rejoin@24:r1 "
                         "slow@4+10:r0:x3' (serve.faults plan grammar); the "
                         "router detects and fails over, the injector never "
                         "announces")
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="write-ahead request journal file (serve.durability):"
                         " every accepted request, emitted token and terminal "
                         "outcome is logged, fsync'd per --sync-every ticks; "
                         "required (auto-defaulted for --trace fleet-poweroff)"
                         " when the fault plan contains poweroff@tick")
    ap.add_argument("--snapshot-dir", default=None,
                    help="warm-snapshot directory: pinned prefix cache (with "
                         "K/V), immune memories and router books, written "
                         "atomically every --snapshot-every ticks")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="warm-snapshot cadence in fleet ticks (0 = off)")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="journal group-commit cadence: one fsync per this "
                         "many ticks (submits always fsync immediately)")
    ap.add_argument("--router", default="immune",
                    choices=("immune", "rr", "jsq"),
                    help="placement policy over the replicas: immune "
                         "(prefix affinity -> anergy draining -> least "
                         "remembered cost), round-robin, or "
                         "join-shortest-queue")
    ap.add_argument("--pin-pages", type=int, default=0,
                    help="pinned prefix-cache budget in pages: refcount-zero "
                         "indexed pages survive up to this many, evicted by "
                         "immune-memory-weighted LRU (0 = legacy free-on-zero)")
    ap.add_argument("--admission", default="preempt",
                    choices=("preempt", "reserve"),
                    help="page admission discipline: admit on current pages "
                         "and preempt the lowest-priority slot on decode "
                         "exhaustion, or legacy worst-case reservation")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="self-speculative decoding: draft K tokens per tick "
                         "through the first --spec-draft-layers layer reps "
                         "and verify them in one batched paged step; greedy "
                         "accept is bitwise-identical to non-speculative "
                         "decode (0 = off; sampled/logprobs ticks fall back)")
    ap.add_argument("--spec-draft-layers", type=int, default=0,
                    help="truncated draft depth in layer repetitions "
                         "(required > 0 and < num_layers with --spec-decode)")
    ap.add_argument("--logprobs", action="store_true",
                    help="record each chosen token's logprob (raw model "
                         "distribution) in the streamed outputs")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature; 0 = exact greedy")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 disables)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k logits filter (0 disables)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base sampling seed; request rid offsets it, so a "
                         "trace replays token-identically")
    ap.add_argument("--show-stream", type=int, default=4,
                    help="print per-token stream deltas for this many "
                         "request ids (0 silences the stream)")
    return ap


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads
    it itself), else the fixed ``<repo>/.jax_cache``. The path is part of
    what makes a later run hit, so it never carries a pid, time or temporary
    name. Library modules and the test suite leave the cache off."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_model(cfg, seed: int = 0):
    """Random weights drawn from ``seed``, plus the zero router bias an MoE
    stack serves with. Returns ``(params, bias)``."""
    params = model_lib.init_params(jax.random.PRNGKey(seed), cfg)
    bias = (jnp.zeros((cfg.num_layers, cfg.num_experts))
            if cfg.num_experts else None)
    return params, bias


def stream_engine_config(args, max_cache: int):
    """The ``--stream`` engine's configuration from the CLI's arguments."""
    from repro.serve.engine import EngineConfig
    return EngineConfig(
        num_slots=args.slots, max_cache=max_cache,
        policy=args.policy, num_classes=3,
        latency_budget=args.latency_budget,
        page_size=args.page_size, num_pages=args.pages,
        prefill_chunk=args.prefill_chunk,
        prefix_sharing=args.prefix_sharing,
        attn_backend=args.attn_backend,
        prefill_streams=args.prefill_streams,
        pin_pages=args.pin_pages,
        admission_mode=args.admission,
        spec_decode=args.spec_decode,
        spec_draft_layers=args.spec_draft_layers)


def replica_devices(n: int) -> list:
    """The device each of ``n`` engine replicas lives on: its own chip when
    the host has at least ``n`` devices, else ``None`` (the default device)
    for every replica."""
    devices = jax.devices()
    return list(devices[:n]) if len(devices) >= n else [None] * n


def main():
    args = build_parser().parse_args()
    enable_compile_cache()

    cfg = configs.get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    params, bias = init_model(cfg)
    if args.restore:
        from repro.configs.base import TrainConfig
        from repro.train import train_step as ts
        like = ts.init_train_state(jax.random.PRNGKey(0), cfg, TrainConfig())
        state, step = ckpt.restore(args.restore, like)
        assert state is not None, f"no checkpoint in {args.restore}"
        params = state.params
        if state.router is not None:
            bias = state.router.bias
        print(f"restored step {step} from {args.restore}")

    if args.stream:
        import math

        from repro.serve import engine as eng_mod
        from repro.serve import traces
        lcm = math.lcm(args.page_size, args.prefill_chunk or 1)
        raw = args.prompt_len + args.steps + 48
        # round up to the page/chunk grain
        ecfg = stream_engine_config(args, max_cache=-(-raw // lcm) * lcm)
        sampling = dict(temperature=args.temperature, top_p=args.top_p,
                        top_k=args.top_k, sample_seed=args.sample_seed)
        if args.trace == "shared-prefix":
            trace = traces.shared_prefix_trace(
                cfg, num_requests=args.requests,
                prefix_len=max(args.prompt_len, 2 * args.page_size),
                decode_lens=(args.steps // 2, args.steps), **sampling)
        elif args.trace == "returning-tenant":
            trace = traces.returning_tenant_trace(
                cfg, prefix_len=max(args.prompt_len, 2 * args.page_size),
                bursts=max(2, args.requests // 12),
                decode_lens=(args.steps // 2,), **sampling)
        elif args.trace == "contention":
            trace = traces.contention_trace(
                cfg, num_requests=args.requests,
                hog_prompt=2 * args.page_size,
                hog_tokens=args.steps, **sampling)
        elif args.trace == "agentic":
            trace = traces.agentic_trace(
                cfg, sessions=max(1, args.requests // 4), turns=4,
                base_prompt=max(args.prompt_len, 2 * args.page_size),
                decode_lens=(args.steps // 2, args.steps), **sampling)
        elif args.trace in ("fleet", "fleet-faults", "fleet-poweroff"):
            fleet_kw = dict(
                num_requests=args.requests,
                prefix_len=max(args.prompt_len, 2 * args.page_size),
                decode_lens=(args.steps // 2, args.steps), **sampling)
            if args.trace == "fleet-faults":
                if args.replicas < 2:
                    ap.error("--trace fleet-faults needs --replicas > 1 "
                             "(faults target replicas behind the router)")
                trace, auto_spec = traces.failover_fleet_trace(
                    cfg, replicas=args.replicas,
                    crash_replica=args.replicas - 1, **fleet_kw)
                args.faults = args.faults or auto_spec
            elif args.trace == "fleet-poweroff":
                if args.replicas < 2:
                    ap.error("--trace fleet-poweroff needs --replicas > 1 "
                             "(the poweroff fault fires through the router's "
                             "fault injector)")
                trace, auto_spec = traces.poweroff_fleet_trace(cfg, **fleet_kw)
                args.faults = args.faults or auto_spec
            else:
                trace = traces.fleet_trace(cfg, **fleet_kw)
        else:
            trace = traces.synthetic_trace(cfg, num_requests=args.requests,
                                           heavy_tokens=args.steps + 8,
                                           **sampling)
        if args.logprobs:
            from dataclasses import replace as _dc_replace
            for req in trace:
                req.params = _dc_replace(req.params, logprobs=True)
        if args.faults and args.replicas < 2:
            ap.error("--faults needs --stream --replicas > 1 (faults target "
                     "replicas behind the router)")
        if args.replicas > 1:
            from repro.serve import router as rt_mod
            poweroff_plan = bool(args.faults) and "poweroff" in args.faults
            if poweroff_plan and not args.journal:
                import tempfile
                args.journal = os.path.join(
                    tempfile.mkdtemp(prefix="serve_wal_"), "journal.wal")
                print(f"poweroff plan with no --journal: journaling to "
                      f"{args.journal}")

            def make_router():
                injector = None
                if args.faults:
                    from repro.serve.faults import FaultInjector, FaultPlan
                    injector = FaultInjector(
                        FaultPlan.parse(args.faults),
                        engine_factory=lambda: eng_mod.Engine(
                            params, cfg, ecfg, router_bias=bias))
                fleet = [eng_mod.Engine(params, cfg, ecfg, router_bias=bias,
                                        device=d)
                         for d in replica_devices(args.replicas)]
                return rt_mod.Router(fleet,
                                     rt_mod.RouterConfig(policy=args.router),
                                     injector=injector)

            if args.faults:
                print(f"fault plan: {args.faults}")
            t0 = time.perf_counter()
            if poweroff_plan:
                from repro.serve import durability
                router, stats = durability.run_durable(
                    make_router, trace, args.journal,
                    snapshot_dir=args.snapshot_dir,
                    snapshot_every=args.snapshot_every,
                    sync_every=args.sync_every,
                    max_ticks=50 * args.requests)
            else:
                router = make_router()
                if args.journal:
                    from repro.serve import durability
                    router.attach_durability(
                        durability.RequestJournal(
                            args.journal, sync_every=args.sync_every),
                        snapshot_dir=args.snapshot_dir,
                        snapshot_every=args.snapshot_every)
                stats = router.run(trace, max_ticks=50 * args.requests)
                if router.journal is not None:
                    router.journal.close()
            dt = time.perf_counter() - t0
            print(f"[{args.router} x {args.replicas}] {stats['completed']} "
                  f"completed / {stats['shed']} shed / {stats['rejected']} "
                  f"rejected of {args.requests} requests in {stats['ticks']} "
                  f"ticks ({dt:.1f}s wall incl. compile)")
            print(f"  throughput {stats['throughput']:.2f} tok/tick | p50 "
                  f"{stats['p50_latency']:.0f} / p99 {stats['p99_latency']:.0f}"
                  f" ticks | goodput {stats['goodput']:.2f}")
            print(f"  placements {stats['placements']} (imbalance "
                  f"{stats['placement_imbalance']:.2f}) | affinity "
                  f"{stats['affinity_hits']}/{stats['affinity_checks']} hits "
                  f"({stats['affinity_tokens']} resident tokens) | "
                  f"{stats['drain_skips']} drain skips / "
                  f"{stats['drain_overflow']} overflow")
            print(f"  fleet: {stats['prefill_tokens']} prefill tokens | "
                  f"{stats['preemptions']} preemptions | "
                  f"{stats['replayed_tokens']} tokens replayed | "
                  f"{stats['pinned_pages_adopted']} pinned pages adopted")
            for i, p in enumerate(stats["per_replica"]):
                print(f"  replica {i}: {p['completed']} completed | "
                      f"p99 {p['p99_latency']:.0f} ticks | pages hw "
                      f"{p['pages_hw']}/{p['pages_budget']} | pinned-hit rate "
                      f"{p['pinned_hit_rate']:.2f}")
            if args.faults:
                print(f"  failover: {stats['deaths']} deaths / "
                      f"{stats['rejoins']} rejoins, "
                      f"{stats['replaced_requests']} re-placed "
                      f"({stats['retries']} retries, {stats['failed']} "
                      f"failed), recovery {stats['recovery_ticks']} ticks, "
                      f"health {stats['health']}")
            if args.journal:
                d = stats["durability"]
                j = d["journal"] or {}
                print(f"  durability: {stats.get('restarts', 0)} restarts | "
                      f"journal {j.get('records', 0)} records / "
                      f"{j.get('syncs', 0)} fsyncs "
                      f"(group commit {j.get('sync_every', 1)}) | "
                      f"recovered {d['recovered_finished']} finished + "
                      f"{d['recovered_open']} replayed | "
                      f"{d['recovered_pinned_pages']} pinned pages warm | "
                      f"{d['snapshots']} snapshots")
            return
        eng = eng_mod.Engine(params, cfg, ecfg, router_bias=bias)
        t0 = time.perf_counter()
        # the streaming front door: RequestOutput deltas per tick, the
        # terminal one carrying the finish reason + latency accounting
        for out in eng.stream(trace, max_ticks=50 * args.requests):
            if out.rid >= args.show_stream or \
                    (not out.new_tokens and not out.finished):
                continue
            tail = f" [{out.finish_reason}, {out.latency_ticks} ticks, " \
                   f"{out.wall_latency_s * 1e3:.0f} ms]" \
                if out.finished and out.latency_ticks is not None \
                else (f" [{out.finish_reason}]" if out.finished else "")
            print(f"  tick {out.tick:4d} req {out.rid} "
                  f"+= {out.new_tokens}{tail}")
        stats = eng.stats()
        dt = time.perf_counter() - t0
        print(f"[{args.policy}] {stats['completed']} completed / "
              f"{stats['shed']} shed / {stats['rejected']} rejected of "
              f"{args.requests} requests in "
              f"{stats['ticks']} ticks ({dt:.1f}s wall incl. compile)")
        print(f"  throughput {stats['throughput']:.2f} tok/tick | "
              f"p50 {stats['p50_latency']:.0f} / p99 {stats['p99_latency']:.0f} "
              f"ticks | p99 wall {stats['p99_wall_ms']:.0f} ms | "
              f"goodput {stats['goodput']:.2f} | "
              f"{stats['mid_stream_admissions']} mid-stream admissions")
        print(f"  sampling: {stats['sampled_requests']} sampled requests "
              f"(temperature {args.temperature}, top-p {args.top_p}, "
              f"top-k {args.top_k}, seed {args.sample_seed})")
        if args.spec_decode:
            print(f"  spec decode: k={stats['spec_decode']} | "
                  f"{stats['spec_ticks']} spec ticks | accept rate "
                  f"{stats['spec_accept_rate']:.2f} "
                  f"({stats['spec_accepted']}/{stats['spec_drafted']} drafts) "
                  f"| {stats['spec_emitted']} tokens emitted speculatively")
        print(f"  paged KV: {stats['pages_hw']}/{stats['pages_budget']} pages "
              f"high-water x {stats['page_size']} tokens | up to "
              f"{stats['concurrency_hw']} concurrent | "
              f"{stats['chunked_prefill_chunks']} prefill chunks landed in "
              f"{stats['prefill_batch_calls']} batched calls "
              f"[{stats['attn_backend']} decode]")
        print(f"  prefix sharing {'on' if stats['prefix_sharing'] else 'off'}:"
              f" hit rate {stats['prefix_hit_rate']:.2f} | "
              f"{stats['shared_pages_adopted']} pages adopted | "
              f"{stats['cow_forks']} CoW forks | "
              f"{stats['nowrite_adoptions']} no-write adoptions | "
              f"{stats['prefill_positions_skipped']} prefill positions "
              f"skipped")
        print(f"  memory hierarchy [{stats['admission_mode']}]: "
              f"pin budget {stats['pin_pages']} pages | "
              f"{stats['pages_pinned']} pinned at exit | {stats['pins']} pins "
              f"/ {stats['pin_evictions']} evictions | pinned-hit rate "
              f"{stats['pinned_hit_rate']:.2f} | {stats['preemptions']} "
              f"preemptions over {stats['preempted_requests']} requests | "
              f"{stats['replayed_tokens']} tokens replayed | "
              f"{stats['prefill_tokens']} prefill tokens computed")
        for r in eng.completed[:4]:
            print(f"  req {r.rid} (class {r.rclass}): arrived {r.arrival}, "
                  f"admitted {r.admit_tick}, finished {r.finish_tick}: "
                  f"{r.out_tokens[:12]}{'...' if len(r.out_tokens) > 12 else ''}")
        return

    key = jax.random.PRNGKey(1)
    prompts = {"tokens": jax.random.randint(key, (args.batch, args.prompt_len),
                                            0, cfg.vocab_size)}
    if cfg.family == "vlm":
        prompts["patches"] = jax.random.normal(
            key, (args.batch, cfg.frontend_tokens, cfg.frontend_dim))
    if cfg.family == "audio":
        prompts["frames"] = jax.random.normal(
            key, (args.batch, args.prompt_len, cfg.frontend_dim))

    t0 = time.perf_counter()
    toks, _ = serve_mod.generate(
        params, cfg, prompts, max_cache=args.prompt_len + args.steps + 8,
        steps=args.steps, router_bias=bias)
    toks.block_until_ready()
    dt = time.perf_counter() - t0
    print(f"{args.batch} x {args.steps} tokens in {dt:.1f}s (incl. compile); "
          f"{args.batch * args.steps / dt:.1f} tok/s")
    for i, row in enumerate(toks):
        print(f"  seq {i}: {row.tolist()}")


if __name__ == "__main__":
    main()
