"""One run of one benchmark cell: build, warm, drive open-loop, check.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file found by name: ``configs/<config>.json``, ``traffic/<mix>.json``,
``cells/<workload>.json`` and ``metrics/<metric>.py``; the architecture
module that a configuration names under ``"bench_arch"`` (``archs/*.py``)
holds its weights, program tree, plain reference and required work. This
module holds the general machinery only and reaches the model through
``Spec.arch``.

A run: make the served weights on the device from the seed; build the
program's engine through the serving CLI's own construction; compile every
program the cell's traffic uses on a few small requests; start the cell's
schedule ``warm_s`` before the window so that slots, pages and the prefix
cache reach steady state (all of that is set-up); measure for ``seconds``;
follow every request due in the window to its end (at most ``drain_s``),
with arrivals still coming; read the chip's peak memory; free the engine;
then check a seeded sample of the served greedy requests against the plain
reference.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

import loadgen
import work

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
TRACE_S = 10.0     # seconds of the window a --trace 1 run profiles


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell's files
# ---------------------------------------------------------------------------
@dataclass
class Spec:
    workload: str
    chips: int
    model: dict               # configs/<config>.json
    arch: object              # the module the file names under "bench_arch"
    mix: loadgen.Mix          # traffic/<mix>.json
    cell: dict                # cells/<workload>.json
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_arch(m: dict, root: str = ROOT):
    """The architecture module that configuration ``m`` names under
    ``"bench_arch"``, by path from ``root``; each file is loaded once."""
    rel = m.get("bench_arch")
    if not rel:
        raise SystemExit(f"bench: configuration {m.get('name')!r} names no "
                         f"architecture module under 'bench_arch'")
    path = os.path.abspath(os.path.join(root, rel))
    if not os.path.isfile(path):
        raise SystemExit(f"bench: configuration {m.get('name')!r}: its "
                         f"'bench_arch' {rel!r} is no file")
    name = "bench_arch_" + "".join(ch if ch.isalnum() else "_"
                                   for ch in os.path.relpath(path, root))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        # registered before it runs, as an import is: a dataclass looks up
        # its module while the class is made
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def load_spec(workload: str, root: str = ROOT) -> Spec:
    bench = _load(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    model = _load(os.path.join(root, conf["file"]))
    return Spec(
        workload=workload, chips=w["chips"], model=model,
        arch=load_arch(model, root),
        mix=loadgen.Mix.from_json(
            _load(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))),
        cell=_load(os.path.join(BENCH, "cells", f"{workload}.json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)])


def reader(name: str):
    """``metrics/<name>.py``'s ``read(run)``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------
def engine_config(m: dict):
    """The serving CLI's ``--stream`` engine at the file's sizes."""
    from repro.launch import serve as serve_cli
    e = m["engine"]
    argv = ["--arch", m["arch"], "--stream", "--slots", str(e["slots"]),
            "--page-size", str(e["page_size"]), "--pages", str(e["pages"]),
            "--prefill-chunk", str(e["prefill_chunk"]),
            "--prefill-streams", str(e["prefill_streams"]),
            "--pin-pages", str(e["pin_pages"]),
            "--latency-budget", str(e["latency_budget"]),
            "--policy", e["policy"], "--admission", e["admission"]]
    return serve_cli.stream_engine_config(
        serve_cli.build_parser().parse_args(argv), e["max_cache"])


# ---------------------------------------------------------------------------
# records of a run
# ---------------------------------------------------------------------------
@dataclass
class Req:
    rid: int
    due: float
    prompt: np.ndarray
    out_len: int
    req: object = None                 # the program's ServeRequest
    submitted: float = 0.0
    admitted: Optional[float] = None   # start of the step that seated it
    times: list = field(default_factory=list)   # host time of each token
    prefill: list = field(default_factory=list)  # (step index, tokens)
    prefilled: int = 0
    end: Optional[float] = None
    reason: Optional[str] = None


@dataclass
class Step:
    start: float
    end: float
    decode: list               # context of each lane that emitted a token
    prefill: list              # (rid, tokens) computed for the first time


@dataclass
class Run:
    """What a reader sees."""
    spec: Spec
    seconds: float
    window: tuple              # (start, end), host clock
    setup_s: float
    requests: list             # every Req, warm-up and drain included
    steps: list
    model: object              # the architecture module's ``work(m)``
    peak: dict
    trace: Optional[dict] = None    # ``tracefile.reduce`` of ``events``
    events: Optional[dict] = None   # the raw trace (``tracefile.extract``)
    traced: Optional[tuple] = None  # (start, end) of the profile, host clock
    pool: dict = field(default_factory=dict)  # KV pool counters

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    @property
    def due(self) -> list:
        """Requests due in the window: the ones the tails and rates judge."""
        return [r for r in self.requests if self.in_window(r.due)]

    @property
    def window_steps(self) -> list:
        return [s for s in self.steps if self.in_window(s.end)]

    @property
    def traced_steps(self) -> list:
        """Steps that ended inside the profiled interval."""
        if self.traced is None:
            return []
        return [s for s in self.steps
                if self.traced[0] <= s.end < self.traced[1]]

    def censored_end(self) -> float:
        """Latest time a request's outcome was observed: an answer still
        missing then counts as arriving no earlier."""
        return max([self.window[1]] + [s.end for s in self.steps])

    def ttft(self, r: Req) -> float:
        if r.times:
            return r.times[0] - r.due
        return self.censored_end() - r.due

    def prefill_positions(self, r: Req) -> list:
        """``(start, count, last)`` of each prefill delta of ``r``: its
        first admission computes positions ``plen - total .. plen - 1``."""
        total = sum(n for _, n in r.prefill)
        pos, out = len(r.prompt) - total, []
        for _, n in r.prefill:
            out.append((pos, n, pos + n == len(r.prompt)))
            pos += n
        return out


def program_seconds(run: Run, name: str) -> list:
    """Device seconds of each execution of the program(s) whose name holds
    ``name``, in the traced window; empty without a trace."""
    if run.trace is None:
        return []
    return [d for prog, ds in run.trace["programs"].items() if name in prog
            for d in ds]


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


# ---------------------------------------------------------------------------
# driving the engine
# ---------------------------------------------------------------------------
def compile_warm(eng, vocab: int, page: int) -> None:
    """Compile every program the cell's traffic runs, on small greedy
    requests: a prompt, then one that adopts its first full page and forks
    its partial one, each a few tokens long. Nothing else is warmed."""
    from repro.serve.api import SamplingParams, ServeRequest
    rng = np.random.default_rng(0)
    base = rng.integers(0, vocab, 2 * page + 3, dtype=np.int32)
    fork = np.concatenate([base[:page + 5],
                           rng.integers(0, vocab, page, dtype=np.int32)])
    for rid, prompt in ((-1, base), (-2, fork)):
        eng.submit(ServeRequest(rid=rid, tokens=prompt,
                                params=SamplingParams(max_new_tokens=3),
                                arrival=eng.tick))
        while eng.queue or eng.jobs or any(s is not None for s in eng.slots):
            eng.step()


class OpenLoop:
    """Open-loop client: submits each request when it is due by the wall
    clock and steps the engine while it holds work."""

    def __init__(self, eng, schedule: list, t0: float):
        self.eng = eng
        self.pending = [(t0 + r.due, r) for r in schedule]   # in due order
        self.next = 0
        self.live: list[Req] = []
        self.requests: list[Req] = []
        self.steps: list[Step] = []
        self.late: list[float] = []            # submit time - due time

    def _submit_due(self, now: float) -> None:
        from repro.serve.api import SamplingParams, ServeRequest
        while self.next < len(self.pending) and \
                self.pending[self.next][0] <= now:
            due, s = self.pending[self.next]
            self.next += 1
            r = Req(rid=len(self.requests), due=due, prompt=s.prompt,
                    out_len=s.out_len)
            r.req = ServeRequest(
                rid=r.rid, tokens=s.prompt,
                params=SamplingParams(max_new_tokens=s.out_len),
                arrival=self.eng.tick)
            r.submitted = time.perf_counter()
            self.late.append(r.submitted - due)
            self.eng.submit(r.req)
            self.requests.append(r)
            self.live.append(r)

    def busy(self) -> bool:
        e = self.eng
        return bool(e.queue or e.jobs or any(s is not None for s in e.slots))

    def step(self, annotate) -> None:
        t0 = time.perf_counter()
        with annotate("bench.step"):
            self.eng.step()
        t1 = time.perf_counter()
        idx = len(self.steps)
        decode, prefill, still = [], [], []
        for r in self.live:
            q = r.req
            if r.admitted is None and q.admit_tick >= 0:
                r.admitted = t0
            d = q.prefill_tokens - r.prefilled
            if d > 0:
                r.prefilled = q.prefill_tokens
                if q.preemptions == 0:        # a re-prefill is recomputation
                    r.prefill.append((idx, d))
                    prefill.append((r.rid, d))
            n, seen = len(q.out_tokens), len(r.times)
            for j in range(max(seen, 1) + 1, n + 1):
                # token j (1-based) came from a decode lane whose input is
                # token j-1, attending the prompt and tokens 1..j-1
                decode.append(len(r.prompt) + j - 1)
            r.times.extend([t1] * (n - seen))
            if q.finish_reason is not None and (
                    q.finish_tick >= 0 or q.finish_reason == "rejected"):
                r.end, r.reason = t1, q.finish_reason
            else:
                still.append(r)
        self.live = still
        self.steps.append(Step(t0, t1, decode, prefill))

    def drive_until(self, t_end: float, annotate, done=None) -> None:
        """Submit and step until ``t_end``, or until ``done()`` holds."""
        while True:
            now = time.perf_counter()
            if now >= t_end or (done is not None and done()):
                return
            self._submit_due(now)
            if self.busy():
                self.step(annotate)
                continue
            wake = min(t_end, self.pending[self.next][0]) \
                if self.next < len(self.pending) else t_end
            time.sleep(max(0.0, min(wake - time.perf_counter(), 0.05)))


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def sample_checked(run: Run, seed: int, target: int) -> list:
    """A seeded sample of the window's greedy requests that finished, the
    longest always among them, until ``target`` served tokens are in it."""
    pool = [r for r in run.due if r.reason == "length"]
    if not pool:
        return []
    longest = max(pool, key=lambda r: (len(r.times), len(r.prompt), -r.rid))
    rest = [r for r in pool if r is not longest]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    out, n = [longest], len(longest.times)
    for i in order:
        if n >= target:
            break
        out.append(rest[i])
        n += len(rest[i].times)
    return out


def logit_gaps(spec: Spec, w: dict, sample: list, quant=None) -> list:
    """Per request, how far below the reference's best logit each served
    token lies; with ``quant``, the gap of the token the control puts first
    instead."""
    import jax.numpy as jnp

    import reference
    model, logits = spec.model, spec.arch.logits
    with_ctx = model["engine"]["max_cache"]
    out = []
    for r in sample:
        served = np.asarray(r.req.out_tokens, np.int32)
        seq = np.concatenate([r.prompt, served[:-1]])
        padded = np.zeros(with_ctx, np.int32)      # one shape for every
        padded[:len(seq)] = seq                    # request: causal, so the
        #                                            tail never reaches back
        idx = np.arange(len(r.prompt) - 1, len(seq))
        ref = logits(w, model, padded)[jnp.asarray(idx)]
        chosen = jnp.asarray(served)
        if quant is not None:
            ctl = logits(w, model, padded, quant=quant)
            chosen = jnp.argmax(ctl[jnp.asarray(idx)], axis=-1)
        out.append(np.asarray(reference.gaps(ref, chosen)))
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
@dataclass
class Served:
    """What driving a cell once leaves behind."""
    run: Run
    memory_peak: int
    late: list
    compiles_in_window: int


def prepare_device(spec: Spec, require_tpu: bool):
    """The device the cell runs on, with JAX's compilation cache on (every
    program kept, however quick to compile, so that a warm checkout's
    set-up only loads); raises SystemExit without the chips it needs."""
    import jax

    from repro.launch import serve as serve_cli
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    if require_tpu and (dev.platform != "tpu"
                        or len(jax.devices()) < spec.chips):
        raise SystemExit(
            f"bench: cell {spec.workload} needs {spec.chips} TPU chip(s); "
            f"JAX found {len(jax.devices())} {dev.platform} device(s) "
            f"({dev.device_kind}); nothing was run")
    serve_cli.enable_compile_cache()
    return dev


def served_weights(spec: Spec, seed: int):
    """The served weights and the program's tree over them; the parameter
    bytes must be the configuration's."""
    import jax

    w = spec.arch.make_weights(spec.model, seed)
    params = spec.arch.program_params(w)
    jax.block_until_ready(params)
    want = spec.arch.work(spec.model).param_bytes
    got = sum(x.nbytes for x in jax.tree.leaves(params))
    if got != want:
        raise SystemExit(f"bench: parameter bytes {got} differ from the "
                         f"configuration's {want}")
    return w, params


def serve(spec: Spec, w: dict, params, seed: int, seconds: float,
          trace: bool, t_start: float,
          rate: Optional[float] = None) -> Served:
    """Build the engine over ``params``, the program's tree over the served
    weights ``w``; warm it, drive the cell's schedule (at ``rate``, default
    the cell's) and follow the window's requests to their end. The engine
    is freed before this returns."""
    import jax
    from contextlib import nullcontext

    from repro.serve.engine import Engine
    m, cell = spec.model, spec.cell
    dev = jax.devices()[0]
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **_: compiles.append(1)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    eng = Engine(params, spec.arch.program_config(m), engine_config(m),
                 **spec.arch.engine_kwargs(w))
    compile_warm(eng, m["vocab_size"], eng.ecfg.page_size)

    warm, drain = float(cell["warm_s"]), float(cell["drain_s"])
    rate = float(cell["rate"] if rate is None else rate)
    t_sched = time.perf_counter()
    w0, w1 = t_sched + warm, t_sched + warm + seconds
    sched = loadgen.schedule(spec.mix, np.random.default_rng(seed),
                             m["vocab_size"],
                             [(0.0, warm, rate), (warm, seconds, rate),
                              (warm + seconds, drain, rate)])
    client = OpenLoop(eng, sched, t_sched)
    annotate = jax.profiler.TraceAnnotation if trace else \
        (lambda name: nullcontext())
    client.drive_until(w0, annotate)
    setup_s = time.perf_counter() - t_start
    n_compiles = len(compiles)
    pool = {"at_window_start": pool_counters(eng.alloc)}
    tdir, traced = None, None
    if trace:
        # the trace covers the window's last TRACE_S seconds: device-trace
        # metrics are per call, and a shorter trace keeps its reading quick.
        # Host spans come from TraceMe (the client's ``bench.*`` spans and
        # JAX's dispatches); the Python tracer's per-call events are left out
        client.drive_until(max(w0, w1 - TRACE_S), annotate)
        tdir = tempfile.TemporaryDirectory()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir.name, profiler_options=opts)
        t_trace = time.perf_counter()
    with annotate("bench.window"):
        client.drive_until(w1, annotate)
    if trace:
        traced = (t_trace, time.perf_counter())
        jax.profiler.stop_trace()
    pool["at_window_end"] = pool_counters(eng.alloc)
    in_window = len(compiles) - n_compiles

    def window_answered():
        return all(r.end is not None for r in client.requests
                   if w0 <= r.due < w1) and not any(
            w0 <= d < w1 for d, _ in client.pending[client.next:])
    client.drive_until(w1 + drain, annotate, done=window_answered)
    ms = dev.memory_stats() or {}
    del eng
    gc.collect()
    events = None
    if trace:
        import tracefile
        events = tracefile.extract(tdir.name)
        tdir.cleanup()
    run = Run(spec=spec, seconds=seconds, window=(w0, w1), setup_s=setup_s,
              requests=client.requests, steps=client.steps,
              model=spec.arch.work(m), traced=traced, pool=pool,
              events=events,
              peak=work.peaks(dev.device_kind) if dev.platform == "tpu"
              else {"bf16_flop_s": 1.0, "hbm_byte_s": 1.0})
    return Served(run=run, memory_peak=int(ms.get("peak_bytes_in_use", 0)),
                  late=client.late, compiles_in_window=in_window)


def pool_counters(alloc) -> dict:
    """The KV pool's occupancy and the prefix cache's counters."""
    return {"pages_in_use": alloc.pages_in_use,
            "pages_pinned": alloc.pages_pinned,
            "usable_pages": alloc.usable_pages, "pins": alloc.pins,
            "pinned_hits": alloc.pinned_hits, "evictions": alloc.evictions}


def read_metrics(run: Run, defs: list) -> dict:
    out = {}
    for mdef in defs:
        v = reader(mdef["name"])(run)
        if v is not None:
            out[mdef["name"]] = {"value": v, "unit": mdef["unit"]}
    return out


def check(run: Run, w: dict, seed: int) -> tuple:
    """(correct, checks): a seeded sample of the window's finished greedy
    requests against the plain reference. The number compared is the mean,
    over the sample's served tokens, of how far each lies below the
    reference's best logit: rounding moves a token only at a near-tie, and
    by little, so the mean grows with the square of the noise, where the
    widest gap grows with the noise alone and does not separate bf16 from
    the int8 control. A request never answered is a failure and counts at
    the top of the TTFT tail; it is not a wrong answer."""
    c = run.spec.cell["check"]
    sample = sample_checked(run, seed, int(c["served_tokens"]))
    gaps = np.concatenate(logit_gaps(run.spec, w, sample)) \
        if sample else None
    checks = {
        "mean_logit_gap": {"value": None if gaps is None
                           else float(gaps.mean()),
                           "limit": c["mean_logit_gap"]},
        "checked_tokens": {"value": 0 if gaps is None else int(gaps.size),
                           "limit": 1}}
    correct = (gaps is not None
               and checks["mean_logit_gap"]["value"] <= c["mean_logit_gap"])
    if gaps is not None:
        log(f"widest gap {float(gaps.max())} (not compared: the control "
            f"does not separate from it)")
    return bool(correct), checks


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool,
             t_start: float, require_tpu: bool = True) -> tuple:
    """One run of a cell. Returns ``(result line, Served, served weights)``;
    raises SystemExit, with no result, when the device is not what the cell
    needs."""
    import jax
    dev = prepare_device(spec, require_tpu)
    log(f"cell {spec.workload}, seed {seed}, {seconds} s, trace {int(trace)}"
        f"; device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {jax.config.jax_compilation_cache_dir}")
    w, params = served_weights(spec, seed)
    sv = serve(spec, w, params, seed, seconds, trace, t_start)
    del params
    run = sv.run
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": sv.memory_peak}
    extra = {}
    if trace:
        import tracefile
        run.trace = tracefile.reduce(run.events,
                                     tracefile.window_of(run.events))
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        extra["breakdown"] = run.trace["breakdown"]
    metrics = read_metrics(run, spec.per_layer if trace else spec.end_to_end)
    due = run.due
    _tails(run, due)
    log(f"generator lateness: median {percentile(sv.late, 50):.4f} s, max "
        f"{max(sv.late):.4f} s over {len(sv.late)} submissions; "
        f"{sv.compiles_in_window} compiles inside the window; "
        f"{sum(r.end is None for r in due)} requests due in the window "
        f"unanswered after the drain")
    correct, checks = check(run, w, seed)
    line = {"correct": correct, "attempted": len(due),
            "failed": sum(r.reason not in ("length", "stop") for r in due),
            "metrics": metrics, "device": device, **extra, "checks": checks}
    return line, sv, w


def log_checks(checks: dict) -> None:
    """Each number compared, beside its limit, on standard error."""
    for k, c in checks.items():
        rel = "at least" if k == "checked_tokens" else "at most"
        log(f"check {k}: {c['value']} ({rel} {c['limit']})")


def _tails(run: Run, due: list) -> None:
    """How many samples each tail rests on, the TTFT statistics, and the KV
    pool's counters at the window's start and end, on standard output."""
    n = len(due)
    gaps = [b - a for r in run.requests for a, b in zip(r.times, r.times[1:])
            if run.in_window(b)]
    ttft = [run.ttft(r) * 1e3 for r in due]
    print(json.dumps({
        "requests_due_in_window": n,
        "beyond_ttft_p90": n - math.ceil(0.9 * n) if n else 0,
        "itl_gaps_in_window": len(gaps),
        "beyond_itl_p95": len(gaps) - math.ceil(0.95 * len(gaps))
        if gaps else 0,
        "ttft_ms": {"p50": percentile(ttft, 50), "p90": percentile(ttft, 90),
                    "mean": float(np.mean(ttft)) if ttft else None},
        "kv_pool": run.pool}), flush=True)
