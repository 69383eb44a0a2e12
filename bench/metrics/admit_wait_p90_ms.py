"""p90, over requests due in the window, of the wait from submission to
the engine's first seating of the request in a slot, both stamped by the
program; a request never seated counts as seated no earlier than the last
step observed. The program's twin of ``queue_wait_p90_ms``, which also
holds the client's wait for the running step to end."""
from harness import percentile


def read(run):
    due = run.due
    if not due or not hasattr(due[0].req, "admit_time"):
        return None
    end = run.censored_end()
    return percentile([((r.req.admit_time if r.req.admit_time >= 0 else end)
                        - r.req.submit_time) * 1e3 for r in due], 90)
