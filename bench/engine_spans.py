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
    for an untraced run."""
    return run.events


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
