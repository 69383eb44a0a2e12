"""Median duration of the engine's own ``engine.step`` span, over the steps
that start in the traced window: the program's twin of ``tick_wall_ms``,
which the client times around the same call."""
from engine_spans import steps
from harness import percentile


def read(run):
    return percentile([(s.end - s.start) * 1e-6 for s in steps(run)], 50)
