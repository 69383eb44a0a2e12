"""Time per step inside the immune admission controller: the outermost
``engine.immune`` spans of the steps that start in the traced window, over
those steps. It holds the controller's host code, its eager device
operations and the reads of its state."""
from engine_spans import IMMUNE, steps


def read(run):
    st = steps(run)
    if not st:
        return None
    total = 0.0
    for step in st:
        end = None                 # of the outermost immune span so far
        for n, s, e in step.spans:          # outer before inner
            if n == IMMUNE and (end is None or s >= end):
                total += e - s
                end = e
    return total * 1e-6 / len(st)
