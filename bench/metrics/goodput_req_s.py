"""Requests due in the window that met both of the cell's limits, per
second of window: first token within ``ttft_limit_ms`` of the due time, and
a mean gap between output tokens within ``itl_limit_ms``. Refused and
unanswered requests meet neither."""


def read(run):
    ttft, itl = run.spec.cell["ttft_limit_ms"], run.spec.cell["itl_limit_ms"]
    good = 0
    for r in run.due:
        if r.reason not in ("length", "stop"):
            continue
        gaps = [b - a for a, b in zip(r.times, r.times[1:])]
        mean_gap_ms = 1e3 * sum(gaps) / len(gaps) if gaps else 0.0
        good += run.ttft(r) * 1e3 <= ttft and mean_gap_ms <= itl
    return good / run.seconds
