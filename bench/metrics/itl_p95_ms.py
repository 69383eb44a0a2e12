"""p95 of every gap between consecutive output tokens of a request, over
the gaps that end inside the window."""
from harness import percentile


def read(run):
    return percentile([(b - a) * 1e3 for r in run.requests
                       for a, b in zip(r.times, r.times[1:])
                       if run.in_window(b)], 95)
