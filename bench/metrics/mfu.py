"""Model FLOPs done in the window over the window's length times the chip's
peak: prefill positions computed for the first time and decoded tokens,
each through every matrix product (the LM head where a token's logits are
needed) and attention over its real context. Preemption replay and
re-prefill are recomputation and do not count."""


def window_flops(run) -> tuple:
    """(prefill FLOPs, decode FLOPs) of the steps that ended in the window."""
    inside = {i for i, s in enumerate(run.steps) if run.in_window(s.end)}
    prefill = 0
    for r in run.requests:
        for (idx, _), (start, n, last) in zip(r.prefill,
                                              run.prefill_positions(r)):
            if idx in inside:
                prefill += run.model.prefill_flops(start, n, last)
    decode = sum(run.model.token_flops(c, True)
                 for i in inside for c in run.steps[i].decode)
    return prefill, decode


def read(run):
    flops = sum(window_flops(run))
    if not flops:
        return None
    return 100.0 * flops / (run.seconds * run.peak["bf16_flop_s"])
