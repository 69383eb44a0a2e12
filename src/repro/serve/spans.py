"""Host spans of the serving engine, written into JAX's own profiler trace.

Each span is a ``jax.profiler.TraceAnnotation``: when a trace is being
taken it lands on the calling thread's line of the profiler's host plane,
on the same clock as the device's program and op events, so every device
idle gap can be named by what the engine's host code was doing in it. When
no trace is being taken a span costs about a microsecond.

``pull`` is the engine's one way to read a device value on the host: the
read blocks until the device has produced the value, and the ``SYNC`` span
around it marks that wait in the trace.
"""
from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation as span  # noqa: F401

STEP = "engine.step"          # Engine.step: one whole tick
ADMIT = "engine.admit"        # shedding, ordering and seating queued requests
PREFILL = "engine.prefill"    # one prefill chunk: pages, inputs, dispatch
PAGES = "engine.pages"        # decode page growth and any preemption it takes
DECODE = "engine.decode"      # the decode tick: dispatch, result, bookkeeping
RETIRE = "engine.retire"      # retiring finished requests
IMMUNE = "engine.immune"      # the immune admission controller's work
SYNC = "engine.sync"          # the host waiting on a device value


def pull(x) -> np.ndarray:
    """``x`` on the host, as a NumPy array, read inside a ``SYNC`` span."""
    with span(SYNC):
        return np.asarray(x)
