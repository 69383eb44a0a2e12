"""Output tokens emitted inside the window, per second of window."""


def read(run):
    return sum(run.in_window(t) for r in run.requests
               for t in r.times) / run.seconds
