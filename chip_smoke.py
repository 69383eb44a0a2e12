#!/usr/bin/env python3
"""Chip smoke test: serve smollm-360m at its published widths on one TPU
through the serving CLI's continuous-batching engine, and check the served
logits against one-shot decode.

    python chip_smoke.py [--seed 0]
    python chip_smoke.py --four-chips [--seed 0]

The default run needs one TPU chip. It builds the engine the way
``python -m repro.launch.serve --stream`` does (chunked prefill, prefix
sharing, preempt admission, XLA paged decode), with bf16 weights drawn from
``--seed``, and serves 32 requests over 16 slots of 2048 tokens: prompts of
256-1536 tokens, half of them behind one of two shared 512-token prefixes,
outputs of 32-128 tokens, a quarter of them sampled. Every greedy request is
then replayed through one-shot ``decode.generate`` on the same chip and the
two logits streams are compared under ``TOLERANCE``.

``--four-chips`` runs only the replica path: a prefix-sharing fleet trace
through the placement router with four replicas, one per chip, compared per
request against one engine on chip 0.

Every line but the last is a smoke fact, not a benchmark number. The last
line is one JSON object naming the device. Any failed phase exits non-zero
before printing it, and so does a host without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from dataclasses import replace

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "smollm-360m"

# Largest |engine logit - one-shot logit| allowed up to and including the
# first token where the two streams differ; a differing token must also be a
# near-tie, its one-shot top-1/top-2 margin under the same bound. The two
# paths round differently: chunked prefill attends over the gathered 2048-
# position pages in 128-token chunks where one-shot prefill attends over the
# prompt alone, and decode runs 16 slots where one-shot runs one sequence.
# The logits are a bf16 product, so they move in bf16 steps: 2**-6 for the
# top logits of this random-weight model, which lie in [2, 4). The bound is
# eight such steps.
TOLERANCE = 8 * 2.0 ** -6


class CompileClock:
    """Backend compile seconds and persistent-cache hits, read off JAX's
    monitoring events (a cache hit is recorded as a short compile)."""

    def __init__(self, jax):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def engine_config(slots: int, max_cache: int, prefill_chunk: int,
                  latency_budget: float):
    """The serving CLI's ``--stream`` engine at these sizes, logits captured.
    The tick budget is set past any latency the trace can reach, so the
    immune admission never sheds a request of this fixed trace."""
    from repro.launch import serve as serve_cli
    args = serve_cli.build_parser().parse_args(
        ["--arch", ARCH, "--stream", "--slots", str(slots),
         "--prefill-chunk", str(prefill_chunk),
         "--latency-budget", str(latency_budget)])
    return serve_cli.stream_engine_config(args, max_cache)._replace(
        capture_logits=True)


def smoke_trace(cfg, seed: int, requests: int = 32, prefix: int = 512,
                unit: int = 256, out_unit: int = 32) -> list:
    """Half system-prompt traffic (two ``prefix``-token prefixes, suffixes of
    1-4 ``unit``s, outputs of 1-4 ``out_unit``s), half bursty traffic
    (prompts of 1 or 3 ``unit``s with ~``out_unit``-token outputs, a heavy
    class of 6 ``unit``s in and 4 ``out_unit``s out); every fourth request
    samples at temperature 0.8, top-p 0.9."""
    from repro.serve import traces
    half = requests // 2
    shared = traces.shared_prefix_trace(
        cfg, num_requests=half, num_prefixes=2, prefix_len=prefix,
        suffix_lens=tuple(k * unit for k in (1, 2, 3, 4)),
        decode_lens=tuple(k * out_unit for k in (1, 2, 3, 4)),
        arrival_every=2, seed=seed)
    bursty = traces.synthetic_trace(
        cfg, num_requests=requests - half, seed=seed + 1,
        light_tokens=out_unit, heavy_tokens=4 * out_unit, heavy_frac=0.25,
        prompt_lens=(unit, 3 * unit), heavy_prompt=6 * unit,
        sample_seed=requests)
    for r in bursty:
        r.rid += half
    trace = shared + bursty
    for r in trace:
        if r.rid % 4 == 3:
            r.params = replace(r.params, temperature=0.8, top_p=0.9)
    return trace


def serve(params, cfg, bias, ecfg, trace, device=None):
    """Serve ``trace`` through ``Engine.stream`` on one engine; every request
    must finish on its token budget. Returns ``(requests by rid, engine,
    RequestOutputs streamed)``."""
    from repro.serve.engine import Engine
    eng = Engine(params, cfg, ecfg, router_bias=bias, device=device)
    outputs = sum(1 for _ in eng.stream(trace, max_ticks=100_000))
    done = {r.rid: r for r in eng.completed}
    bad = [(r.rid, r.finish_reason) for r in trace
           if r.rid not in done or r.finish_reason != "length"
           or len(r.out_tokens) != r.params.max_new_tokens]
    if bad:
        fail(f"requests not served to their budget: {bad}")
    return done, eng, outputs


def check_against(served: dict, ref_tokens: dict, ref_logits: dict,
                  tol: float) -> dict:
    """Compare each request's tokens and captured logits rows with a
    reference stream. Up to and including the first differing token the
    largest |logit difference| must stay under ``tol``; at that token the
    reference's top-1/top-2 margin must be under ``tol`` too. Returns a
    summary; raises on a breach."""
    exact, max_delta, divergences = 0, 0.0, []
    for rid in sorted(ref_tokens):
        req = served[rid]
        got = np.asarray(req.out_tokens)
        n = len(got)
        ref_t = np.asarray(ref_tokens[rid])[:n]
        got_l = np.stack(req.out_logits)
        ref_l = np.asarray(ref_logits[rid])[:n]
        if not np.isfinite(got_l).all():
            fail(f"request {rid}: non-finite served logits")
        diff = np.flatnonzero(got != ref_t)
        upto = int(diff[0]) + 1 if diff.size else n
        delta = float(np.abs(got_l[:upto] - ref_l[:upto]).max())
        max_delta = max(max_delta, delta)
        if delta >= tol:
            fail(f"request {rid}: |dlogit| {delta} >= tolerance {tol} "
                 f"within its first {upto} tokens")
        if not diff.size:
            exact += np.array_equal(got_l, ref_l)
            continue
        pos = int(diff[0])
        top2 = np.sort(ref_l[pos])[-2:]
        margin = float(top2[1] - top2[0])
        divergences.append((rid, pos, margin))
        if margin >= tol:
            fail(f"request {rid}: tokens differ at {pos} where the reference "
                 f"margin {margin} is no near-tie (tolerance {tol})")
    return {"requests": len(ref_tokens), "logits_bitwise": exact,
            "max_delta": max_delta, "divergences": divergences}


def oneshot_reference(params, cfg, bias, served: dict, max_cache: int):
    """One-shot ``decode.generate`` of every greedy request's prompt, one
    request at a time, with the same cache length. Returns token and logits
    rows per rid."""
    import jax.numpy as jnp

    from repro.serve import decode
    greedy = [r for r in served.values() if r.params.is_greedy]
    steps = max(len(r.out_tokens) for r in greedy)
    tokens, logits = {}, {}
    for r in greedy:
        t, _, lg = decode.generate(
            params, cfg, {"tokens": jnp.asarray(r.tokens)[None]},
            max_cache=max_cache, steps=steps, router_bias=bias,
            return_logits=True)
        tokens[r.rid], logits[r.rid] = np.asarray(t[0]), np.asarray(lg[0])
    return tokens, logits


def serve_and_compare(params, cfg, bias, *, slots: int, max_cache: int,
                      prefill_chunk: int, trace: list, warmup: list,
                      clock: CompileClock, tol: float = TOLERANCE) -> dict:
    """The one-chip phase: warm the engine's compiled steps up on
    ``warmup``, serve ``trace`` through ``Engine.stream``, replay its greedy
    requests one-shot and compare. Returns the facts it printed."""
    import jax
    ecfg = engine_config(slots, max_cache, prefill_chunk, 10.0 * max_cache)
    c0 = clock.seconds
    t0 = time.perf_counter()
    serve(params, cfg, bias, ecfg, warmup)
    say(f"warm-up: {len(warmup)} requests in "
        f"{time.perf_counter() - t0:.3f} s wall, of which backend compile "
        f"{clock.seconds - c0:.3f} s over {clock.compiles} compiles, "
        f"{clock.cache_hits} persistent-cache hits")
    n0 = clock.compiles
    t0 = time.perf_counter()
    served, eng, outputs = serve(params, cfg, bias, ecfg, trace)
    serve_s = time.perf_counter() - t0
    tokens = sum(len(r.out_tokens) for r in served.values())
    st = eng.stats()
    sampled = sum(not r.params.is_greedy for r in trace)
    kv_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.pool))
    ms = jax.devices()[0].memory_stats() or {}
    say(f"serve: {len(trace)} requests ({len(trace) - sampled} greedy, "
        f"{sampled} sampled), {tokens} tokens, {outputs} stream outputs, "
        f"{st['ticks']} ticks in {serve_s:.3f} s wall; "
        f"{clock.compiles - n0} compiles while serving")
    say(f"engine: {slots} slots x {max_cache} tokens, page {ecfg.page_size}, "
        f"prefill chunk {prefill_chunk}, KV pool {kv_bytes} bytes; prefix "
        f"hit rate {st['prefix_hit_rate']:.3f}, "
        f"{st['shared_pages_adopted']} pages adopted, {st['preemptions']} "
        f"preemptions, {st['prefill_tokens']} prefill tokens computed")
    say(f"memory after serving: bytes_in_use "
        f"{ms.get('bytes_in_use', 'not reported')}, peak "
        f"{ms.get('peak_bytes_in_use', 'not reported')}")
    ref_t, ref_l = oneshot_reference(params, cfg, bias, served, max_cache)
    res = check_against(served, ref_t, ref_l, tol)
    say(f"one-shot compare: {res['requests']} greedy requests, "
        f"{res['logits_bitwise']} with bitwise-equal logits, max |dlogit| "
        f"{res['max_delta']} (tolerance {tol}); token divergences "
        f"(rid, position, one-shot top-2 margin): {res['divergences']}")
    return {**res, "tokens": tokens, "kv_bytes": kv_bytes}


def four_chip_phase(params, cfg, bias, *, slots: int, max_cache: int,
                    prefill_chunk: int, trace_fn, tol: float = TOLERANCE
                    ) -> dict:
    """Four replicas behind the placement router, one per device, against
    one engine on the first device, on two copies of one fleet trace.
    Placement must not change what a request computes."""
    import jax

    from repro.launch import serve as serve_cli
    from repro.serve import router as rt
    from repro.serve.engine import Engine
    devices = serve_cli.replica_devices(4)
    if any(d is None for d in devices):
        fail(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    ecfg = engine_config(slots, max_cache, prefill_chunk, 10.0 * max_cache)
    fleet = [Engine(params, cfg, ecfg, router_bias=bias, device=d)
             for d in devices]
    trace = trace_fn()
    t0 = time.perf_counter()
    stats = rt.Router(fleet, rt.RouterConfig(policy="immune")).run(
        trace, max_ticks=100_000)
    fleet_s = time.perf_counter() - t0
    routed = {r.rid: r for e in fleet for r in e.completed}
    if len(routed) != len(trace):
        fail(f"router completed {len(routed)} of {len(trace)} requests")
    say(f"router: {len(trace)} requests over 4 replicas in {stats['ticks']} "
        f"ticks, {fleet_s:.3f} s wall; placements "
        f"{stats['placements']}, affinity hits {stats['affinity_hits']}")
    replica_bytes = (sum(x.nbytes for x in jax.tree.leaves(fleet[0].params))
                     + sum(x.nbytes for x in jax.tree.leaves(fleet[0].pool)))
    for i, (d, e) in enumerate(zip(devices, fleet)):
        homes = {dd for x in jax.tree.leaves((e.params, e.pool))
                 for dd in x.devices()}
        ms = d.memory_stats() or {}
        say(f"device {i}: replica {i}'s arrays on "
            f"{sorted(map(str, homes))}; bytes_in_use "
            f"{ms.get('bytes_in_use', 'not reported')}, peak "
            f"{ms.get('peak_bytes_in_use', 'not reported')}")
        if homes != {d}:
            fail(f"replica {i} is not on device {d}: {homes}")
        if ms.get("bytes_in_use", replica_bytes) < replica_bytes:
            fail(f"device {i} holds {ms['bytes_in_use']} bytes, less than "
                 f"one replica's {replica_bytes}")
    del fleet
    single, _, _ = serve(params, cfg, bias, ecfg, trace_fn(),
                         device=devices[0])
    ref_t = {rid: single[rid].out_tokens for rid in single}
    ref_l = {rid: np.stack(single[rid].out_logits) for rid in single}
    res = check_against(routed, ref_t, ref_l, tol)
    say(f"router vs one engine: {res['requests']} requests, "
        f"{res['logits_bitwise']} with bitwise-equal logits, max |dlogit| "
        f"{res['max_delta']} (tolerance {tol}); token divergences "
        f"(rid, position, one-engine top-2 margin): {res['divergences']}")
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of the traces")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica router phase")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform} "
              f"({dev.device_kind}), nothing was run", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import configs
    from repro.launch import serve as serve_cli
    from repro.serve import api, traces

    cache = serve_cli.enable_compile_cache()
    clock = CompileClock(jax)
    cfg = configs.get_config(ARCH)
    say(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {cache}")
    say(f"model {cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.num_heads}/{cfg.num_kv_heads} x {cfg.head_dim}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}, seed {args.seed}")
    params, bias = serve_cli.init_model(cfg, args.seed)
    say(f"params {sum(x.nbytes for x in jax.tree.leaves(params))} bytes")
    sizes = dict(slots=16, max_cache=2048, prefill_chunk=128)
    if args.four_chips:
        four_chip_phase(params, cfg, bias, **sizes, trace_fn=lambda: (
            traces.fleet_trace(cfg, tenants=3, num_requests=32,
                               prefix_len=512, suffix_lens=(128, 256),
                               decode_lens=(32, 64), seed=args.seed)))
    else:
        rng = np.random.default_rng(args.seed)
        warmup = [api.ServeRequest(  # one greedy, one sampled
            rid=i, tokens=rng.integers(0, cfg.vocab_size, 256, np.int32),
            params=api.SamplingParams(temperature=0.8 * i, top_p=0.9,
                                      max_new_tokens=4))
            for i in range(2)]
        serve_and_compare(params, cfg, bias, **sizes,
                          trace=smoke_trace(cfg, args.seed), warmup=warmup,
                          clock=clock)
    say(f"backend compile {clock.seconds:.3f} s over {clock.compiles} "
        f"compiles, {clock.cache_hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
