"""Share of the requests due in the window that the engine preempted at
least once."""


def read(run):
    due = run.due
    if not due:
        return None
    return 100.0 * sum(r.req.preemptions > 0 for r in due) / len(due)
