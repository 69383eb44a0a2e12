"""Share of the prompt tokens admitted in the window that were not
prefilled: served from resident pages, live or pinned. Read from each
request's own prefill counter over its first admission."""


def read(run):
    admitted = [r for r in run.requests
                if r.admitted is not None and run.in_window(r.admitted)
                and r.req.preemptions == 0 and r.times]
    total = sum(len(r.prompt) for r in admitted)
    if not total:
        return None
    skipped = sum(len(r.prompt) - sum(n for _, n in r.prefill)
                  for r in admitted)
    return 100.0 * skipped / total
