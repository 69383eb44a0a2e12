"""The decode tick's share of its roofline: the least time the chip needs
for the work the model requires per tick (parameters once, each emitting
lane's live K/V and the position it writes, 2 x params FLOPs per lane plus
attention over its real context, as the architecture module's ``work``
counts it: ``run.model``), over the measured device time per tick. Both
means are taken over the profiled interval alone: the ticks of the steps
that ended in it, and the device time of the tick programs it recorded."""
import work
from harness import program_seconds


def read(run):
    calls = program_seconds(run, "decode_tick")
    ticks = [s for s in run.traced_steps if s.decode]
    if not calls or not ticks:
        return None
    least = [work.least_time(*run.model.decode_tick(t), run.peak)[0]
             for t in ticks]
    return 100.0 * (sum(least) / len(least)) / (sum(calls) / len(calls))
