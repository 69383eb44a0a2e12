"""Reads of a device value by the host per step: ``engine.sync`` spans in
the steps that start in the traced window, over those steps. Each one
blocks the host until the device has produced the value."""
from engine_spans import SYNC, steps


def read(run):
    st = steps(run)
    if not st:
        return None
    return sum(n == SYNC for x in st for n, _, _ in x.spans) / len(st)
