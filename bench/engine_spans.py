"""The serving engine's own host spans in a traced run, for the per-layer
metrics that read them.

The program writes ``engine.*`` spans into JAX's profiler trace (its
``serve/spans.py``): one ``engine.step`` per tick, holding one span per
phase, ``engine.immune`` around the immune controller's work and
``engine.sync`` around every read of a device value. They land on the
client thread's line, which ``tracefile.extract`` keeps, on the clock of
the device's events. A program without them gives no steps, and every
reader built on this module then returns nothing.
"""
from __future__ import annotations

import sys
from bisect import bisect_left
from typing import NamedTuple, Optional

import tracefile

STEP, IMMUNE, SYNC = "engine.step", "engine.immune", "engine.sync"


class Step(NamedTuple):
    start: float               # ns, the trace's clock
    end: float
    spans: list                # (name, start, end) of the engine spans in it


def events(run) -> Optional[dict]:
    """The raw events of ``run``'s trace (``tracefile.extract``), or None
    for an untraced run.

    The harness hands a reader the run with the trace's reduction only, and
    keeps the raw events on the ``Served`` record that holds the run, in a
    calling frame: they are taken from there where the run does not carry
    them itself."""
    ev = getattr(run, "events", None)
    if ev is not None or getattr(run, "trace", None) is None:
        return ev
    frame = sys._getframe(1)
    while frame is not None:
        for v in frame.f_locals.values():
            if getattr(v, "run", None) is run \
                    and getattr(v, "trace_events", None) is not None:
                return v.trace_events
        frame = frame.f_back
    return None


def steps(run) -> list:
    """The ``engine.step`` spans that start in the traced window, each with
    the engine spans inside it, outer before inner; empty without a trace
    or without engine spans."""
    ev = events(run)
    if ev is None:
        return []
    lo, hi = tracefile.window_of(ev)
    eng = sorted((s, -(s + d), n) for n, s, d in ev["host"]
                 if n.startswith("engine."))
    starts = [s for s, _, _ in eng]
    out = []
    for s, neg_end, n in eng:
        if n == STEP and lo <= s < hi:
            e = -neg_end
            inside = eng[bisect_left(starts, s):bisect_left(starts, e)]
            out.append(Step(s, e, [(m, a, -b) for a, b, m in inside
                                   if m != STEP]))
    return out
