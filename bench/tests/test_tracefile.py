"""The reduction from trace events to busy time, per-program device time
and the breakdown."""
import json
import os

import pytest

import tracefile
from conftest import DATA

MS = 1_000_000


def synthetic():
    """Two programs on one device inside a 100 ms window: a 30 ms decode
    tick (ops 0-10 and 12-30 ms) and a 20 ms prefill chunk (50-70 ms); the
    host dispatched the chunk from 36 to 44 ms and slept after 75 ms."""
    return {
        "devices": {"/device:TPU:0": {
            "modules": [["jit__decode_tick", 0, 30 * MS],
                        ["jit__prefill_chunk", 50 * MS, 20 * MS]],
            "ops": [["fusion.1", 0, 10 * MS], ["copy.2", 12 * MS, 18 * MS],
                    ["fusion.3", 50 * MS, 20 * MS]]}},
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.step", 0, 45 * MS],
                 ["PjitFunction(_prefill_chunk)", 36 * MS, 8 * MS],
                 ["bench.step", 45 * MS, 30 * MS]]}


def test_busy_idle_and_programs():
    ev = synthetic()
    r = tracefile.reduce(ev, tracefile.window_of(ev))
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.048)
    assert r["programs"] == {"jit__decode_tick": [pytest.approx(0.03)],
                             "jit__prefill_chunk": [pytest.approx(0.02)]}
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["jit__decode_tick/copy.2"] == pytest.approx(0.018)
    assert ops["jit__prefill_chunk/fusion.3"] == pytest.approx(0.02)
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps == [("bench.window", pytest.approx(0.03)),
                    ("PjitFunction(_prefill_chunk)", pytest.approx(0.02)),
                    ("bench.step", pytest.approx(0.002))]


def test_gap_is_named_by_the_innermost_engine_span():
    """Inside the engine's spans a gap takes the innermost ``engine.*``
    name, whatever JAX's own spans lie inside it."""
    host = [["bench.window", 0, 100], ["bench.step", 0, 90],
            ["engine.step", 1, 88], ["engine.immune", 10, 30],
            ["PjitFunction(multiply)", 12, 5], ["ParseArguments", 13, 2],
            ["engine.sync", 50, 20], ["np.asarray", 55, 10]]
    assert tracefile._host_path(host, 14) == "engine.immune"
    assert tracefile._host_path(host, 60) == "engine.sync"
    assert tracefile._host_path(host, 95) == "bench.window"
    assert tracefile._host_path(host, 100) == "host idle"


def test_window_clips_events():
    ev = synthetic()
    r = tracefile.reduce(ev, (20 * MS, 60 * MS))
    assert r["busy_s"] == pytest.approx(0.020)
    assert list(r["programs"]) == ["jit__prefill_chunk"]


def test_no_device_events_is_an_error():
    with pytest.raises(ValueError):
        tracefile.reduce({"devices": {}, "host": []}, (0, 1))


def test_recorded_v5e_trace():
    """A slice of a real traced window (one TPU v5 lite, smollm-360m.agent):
    the decode ticks are ~90 ms of device time each, the device is busy
    most of the slice, and every idle gap is attributed to a host span."""
    with open(os.path.join(DATA, "trace_v5e_agent.json")) as f:
        ev = json.load(f)
    r = tracefile.reduce(ev, tuple(ev["window"]))
    assert r["window_s"] == pytest.approx(0.45)
    assert 0.5 < r["busy_s"] / r["window_s"] < 1.0
    ticks = r["programs"]["jit__decode_tick"]
    assert ticks and all(0.05 < t < 0.2 for t in ticks)
    ops = r["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops[0][0].startswith("jit__decode_tick/")
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps and all(name != "host idle" for name, _ in gaps)
