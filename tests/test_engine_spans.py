"""The engine's host spans in JAX's profiler trace (``serve.spans``).

A scripted greedy trace runs once under the profiler and once without it;
the spans are read back from the trace's host plane. Every test that takes
a profiler trace of the engine lives in this file: a process holds one
trace at a time.
"""
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import configs
from repro.models import model
from repro.serve import engine as eng_mod
from repro.serve import spans
from repro.serve.api import SamplingParams, ServeRequest

jax.config.update("jax_platform_name", "cpu")

# two slots, one-chunk prompts, one prefill stream: the tick-by-tick work is
# fixed, so the device reads of every step can be counted by hand
ECFG = eng_mod.EngineConfig(num_slots=2, max_cache=32, page_size=8,
                            prefill_chunk=8, policy="immune")

# engine.sync spans per step of the script below:
#   tick 0: admit (2 queued x admissible, throttled, predicted costs) 4,
#           request 0's prefill lands (seed token) 1, decode (tokens, finite
#           flags) 2, end of tick (predicted costs) 1
#   tick 1: the queue is empty so admission returns at once; request 1's
#           prefill lands 1, decode 2, end of tick 1
#   ticks 2-5: decode 2, end of tick 1 (request 0 retires at tick 2, request
#           1 at tick 5; a retirement reads nothing)
SYNCS = [8, 4, 3, 3, 3, 3]


@pytest.fixture(scope="module")
def smoke():
    cfg = configs.get_config("smollm-360m").smoke()
    return cfg, model.init_params(jax.random.PRNGKey(0), cfg)


def _script(cfg):
    rng = np.random.default_rng(0)
    return [ServeRequest(rid=i, tokens=rng.integers(0, cfg.vocab_size, 6,
                                                    dtype=np.int32),
                         params=SamplingParams(max_new_tokens=n))
            for i, n in enumerate((4, 6))]


def _serve(smoke):
    cfg, params = smoke
    eng = eng_mod.Engine(params, cfg, ECFG)
    reqs = _script(cfg)
    eng.run(reqs)
    return eng, reqs


def _engine_spans(trace_dir):
    """``(name, start_ns, end_ns)`` of every ``engine.*`` host span."""
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    return sorted((e.name, e.start_ns, e.start_ns + e.duration_ns)
                  for p in ProfileData.from_file(path).planes
                  if p.name == "/host:CPU" for line in p.lines
                  for e in line.events if e.name.startswith("engine."))


@pytest.fixture(scope="module")
def traced(smoke, tmp_path_factory):
    tdir = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        eng, reqs = _serve(smoke)
    finally:
        jax.profiler.stop_trace()
    ev = _engine_spans(tdir)
    steps = [(s, e) for n, s, e in ev if n == spans.STEP]
    inside = [[(n, s, e) for n, s, e in ev if n != spans.STEP and a <= s < b]
              for a, b in steps]
    return eng, reqs, steps, inside


def test_one_step_span_per_tick(traced):
    eng, _, steps, inside = traced
    assert len(steps) == eng.tick == len(SYNCS)
    for (a, b), kids in zip(steps, inside):
        assert all(a <= s <= e <= b for _, s, e in kids)


def test_every_step_holds_its_phases_in_tick_order(traced):
    _, _, _, inside = traced
    for kids in inside:
        first = {}
        for n, s, _ in kids:
            first.setdefault(n, s)
        order = [spans.ADMIT, spans.PREFILL, spans.PAGES, spans.DECODE,
                 spans.RETIRE]
        assert [first[n] for n in order] == sorted(first[n] for n in order)
        # the tick ends with the immune controller's update
        last_immune = max(s for n, s, _ in kids if n == spans.IMMUNE)
        assert last_immune > first[spans.RETIRE]


def test_sync_spans_per_step_are_the_hand_count(traced):
    _, _, _, inside = traced
    assert [sum(n == spans.SYNC for n, _, _ in kids)
            for kids in inside] == SYNCS


def test_outputs_are_bitwise_the_same_with_the_profiler_on_and_off(traced,
                                                                    smoke):
    _, on, _, _ = traced
    _, off = _serve(smoke)
    assert [r.out_tokens for r in on] == [r.out_tokens for r in off]
    assert all(len(r.out_tokens) == r.max_new_tokens for r in on)


def test_admit_time_is_the_first_seating_and_survives_preemption(smoke):
    """Pages for one worst case and two requests: both seat on their prompt
    pages, decode growth evicts the later one, and it is seated again."""
    cfg, params = smoke
    ecfg = eng_mod.EngineConfig(num_slots=2, max_cache=32, page_size=16,
                                num_pages=3, policy="fifo")
    eng = eng_mod.Engine(params, cfg, ecfg)
    rng = np.random.default_rng(1)
    reqs = [ServeRequest(rid=i, tokens=rng.integers(0, cfg.vocab_size, 10,
                                                    dtype=np.int32),
                         params=SamplingParams(max_new_tokens=8))
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    first = {}
    while eng.queue or any(s is not None for s in eng.slots):
        before = eng.tick
        eng.step()
        for r in reqs:
            if r.admit_time >= 0:
                first.setdefault(r.rid, (r.admit_time, before))
    for r in reqs:
        t, tick = first[r.rid]
        assert r.admit_time == t and r.admit_tick == tick
        assert r.submit_time <= r.admit_time <= r.finish_time
    assert reqs[1].preemptions >= 1 and reqs[0].preemptions == 0
