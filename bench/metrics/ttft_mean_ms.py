"""Mean, over every request due in the window, of the time from its due
time to its first token; a request never answered counts as answered no
earlier than the last step observed."""


def read(run):
    due = run.due
    if not due:
        return None
    return 1e3 * sum(run.ttft(r) for r in due) / len(due)
