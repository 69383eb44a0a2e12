"""Device time per prefill-chunk program execution in the traced window."""
from harness import program_seconds


def read(run):
    calls = program_seconds(run, "prefill_chunk")
    return 1e3 * sum(calls) / len(calls) if calls else None
