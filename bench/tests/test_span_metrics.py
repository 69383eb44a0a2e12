"""The per-layer metrics read from the engine's own host spans
(``engine_spans.py``), on synthetic events and on a recorded chip trace."""
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import engine_spans
import harness
import tracefile
from conftest import DATA

MS = 1_000_000
TRACE_READERS = ("step_ms", "immune_ms", "syncs_per_step", "host_idle_share")


def synthetic():
    """A 100 ms window on one device, busy from 20 to 50 ms (a decode tick)
    and from 60 to 80 ms. Two engine steps start in it; a third, from -10 to
    3 ms, started before it. In the first step the host waits on the tick
    from 21 to 50 ms, and the device then idles under the immune
    controller's end-of-tick update (54-70 ms, with an inner call)."""
    spans = [
        ("bench.window", 0, 100),
        ("bench.step", -11, 3.5), ("engine.step", -10, 3),
        ("engine.sync", -5, 1),
        ("bench.step", 4, 91), ("engine.step", 5, 90),
        ("engine.admit", 5, 15),
        ("engine.immune", 6, 10), ("engine.sync", 7, 9),
        ("engine.immune", 11, 13), ("engine.sync", 11, 12),
        ("engine.prefill", 15, 18), ("engine.pages", 18, 19),
        ("engine.decode", 19, 52),
        ("engine.sync", 21, 50), ("engine.sync", 50, 51),
        ("engine.retire", 52, 54),
        ("engine.immune", 54, 70), ("engine.immune", 55, 56),
        ("engine.sync", 56, 57),
        ("bench.step", 91.5, 99.5), ("engine.step", 92, 99),
        ("engine.admit", 92, 93), ("engine.decode", 93, 97),
        ("engine.sync", 94, 95), ("engine.immune", 97, 98.5)]
    return {
        "devices": {"/device:TPU:0": {
            "modules": [["jit__decode_tick", 20 * MS, 30 * MS],
                        ["jit__prefill_chunk", 60 * MS, 20 * MS]],
            "ops": [["fusion.1", 20 * MS, 30 * MS],
                    ["fusion.2", 60 * MS, 20 * MS]]}},
        "host": [[n, s * MS, (e - s) * MS] for n, s, e in spans]}


# step_ms: the median of 85 and 7 ms. immune_ms: outermost immune spans of
# 4 + 2 + 16 ms and 1.5 ms over two steps. syncs_per_step: five and one.
# host_idle_share: in a step, in no sync, device idle: 1-3, 5-7, 9-11,
# 12-20, 51-56, 57-60, 80-90, 92-94 and 95-99 ms, 38 of 100 ms.
@pytest.mark.parametrize("name, want", [
    ("step_ms", 46.0), ("immune_ms", 11.75), ("syncs_per_step", 3.0),
    ("host_idle_share", 38.0)])
def test_reader_on_synthetic_spans(name, want):
    run = SimpleNamespace(events=synthetic())
    assert harness.reader(name)(run) == pytest.approx(want)


def test_idle_under_the_immune_update_is_named_by_it():
    ev = synthetic()
    gaps = dict(tracefile.reduce(ev, tracefile.window_of(ev))
                ["breakdown"]["idle_gaps"])
    assert gaps["engine.immune"] == pytest.approx(0.01)


@pytest.mark.parametrize("name", TRACE_READERS)
def test_untraced_or_without_engine_spans_reads_nothing(name):
    read = harness.reader(name)
    assert read(SimpleNamespace(events=None, trace=None)) is None
    ev = synthetic()
    ev["host"] = [e for e in ev["host"] if not e[0].startswith("engine.")]
    assert read(SimpleNamespace(events=ev)) is None


def _run(window=(0.0, 10.0), requests=(), trace=None, events=None):
    return harness.Run(spec=None, seconds=window[1] - window[0],
                       window=window, setup_s=0.0, requests=list(requests),
                       steps=[harness.Step(0.0, 11.0, [], [])], model=None,
                       peak={}, trace=trace, events=events)


def test_events_are_found_on_the_record_that_holds_the_run():
    """The run the harness hands readers carries the raw events."""
    ev = synthetic()
    run = _run(trace={"busy_s": 0.05, "window_s": 0.1}, events=ev)
    assert engine_spans.events(run) is ev
    assert harness.reader("syncs_per_step")(run) == pytest.approx(3.0)
    assert engine_spans.events(_run()) is None


def _req(due, submit, admit):
    r = harness.Req(rid=0, due=due, prompt=np.zeros(4, np.int32), out_len=1)
    r.req = SimpleNamespace(submit_time=submit, admit_time=admit)
    return r


def test_admit_wait_p90():
    """Nine requests due in the window waited 0-8 ms and a tenth was never
    seated (counted to the last step, at 11 s); one due before the window
    is left out."""
    reqs = [_req(1.0 + i, 1.0 + i, 1.0 + i + 1e-3 * i) for i in range(9)]
    reqs += [_req(9.5, 9.5, -1.0), _req(-1.0, -1.0, 5.0)]
    read = harness.reader("admit_wait_p90_ms")
    want = np.percentile([float(i) for i in range(9)] + [1500.0], 90)
    assert read(_run(requests=reqs)) == pytest.approx(want)
    older = _req(2.0, 2.0, 0.0)
    older.req = SimpleNamespace(submit_time=2.0)   # a program without it
    assert read(_run(requests=[older])) is None


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_v5e_agent_spans.json")) as f:
        return json.load(f)


def test_recorded_trace_shares_one_clock(recorded):
    """A slice of a traced window of smollm-360m.agent on one TPU v5 lite:
    every decode tick starts on the device inside the host's
    ``engine.decode`` span that dispatched it."""
    decode = sorted((s, s + d) for n, s, d in recorded["host"]
                    if n == "engine.decode")
    ticks = [s for dev in recorded["devices"].values()
             for n, s, _ in dev["modules"] if n == "jit__decode_tick"]
    assert ticks and decode
    for t in ticks:
        assert any(a <= t < b for a, b in decode), t


def test_recorded_trace_reads_every_span_metric(recorded):
    run = SimpleNamespace(events=recorded)
    got = {n: harness.reader(n)(run) for n in TRACE_READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["syncs_per_step"] >= 3       # two decode reads and the immune
    r = tracefile.reduce(recorded, tracefile.window_of(recorded))
    assert got["host_idle_share"] <= 100 * (1 - r["busy_s"] / r["window_s"])
    assert got["step_ms"] == pytest.approx(np.median(
        [(s.end - s.start) * 1e-6 for s in engine_spans.steps(run)]))


def test_recorded_idle_is_named_by_the_engine_spans(recorded):
    """The reduction names the slice's longest idle gaps, all inside engine
    steps, after the engine span the host was in."""
    r = tracefile.reduce(recorded, tracefile.window_of(recorded))
    names = [n for n, _ in r["breakdown"]["idle_gaps"]]
    assert names and all("engine." in n for n in names), names
