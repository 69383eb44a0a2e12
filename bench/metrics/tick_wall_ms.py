"""Median host time of one engine step in the window."""
from harness import percentile


def read(run):
    return percentile([(s.end - s.start) * 1e3 for s in run.window_steps], 50)
