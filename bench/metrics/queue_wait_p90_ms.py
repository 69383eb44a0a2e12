"""p90, over requests due in the window, of the wait from the due time to
the start of the engine step that first seated the request in a slot."""
from harness import percentile


def read(run):
    end = run.censored_end()
    return percentile([((r.admitted if r.admitted is not None else end)
                        - r.due) * 1e3 for r in run.due], 90)
