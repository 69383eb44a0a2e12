"""Share of the traced window in which no operation runs on the device
while the host is inside ``engine.step`` and in no ``engine.sync``: the
device's idle time that the engine's own host code causes, a part of
``device_idle_share``. Averaged over the devices traced."""
import tracefile
from engine_spans import STEP, SYNC, events


def minus(a: list, b: list) -> list:
    """Merged intervals ``a`` with merged intervals ``b`` taken out; both
    in order, as ``tracefile._union`` gives them."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append([s, b[k][0]])
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append([s, e])
    return out


def read(run):
    ev = events(run)
    if ev is None:
        return None
    step = [x for x in ev["host"] if x[0] == STEP]
    if not step:
        return None
    lo, hi = tracefile.window_of(ev)
    host = minus(tracefile._union(step, lo, hi),
                 tracefile._union([x for x in ev["host"] if x[0] == SYNC],
                                  lo, hi))
    idle = [sum(e - s for s, e in minus(
        host, tracefile._union(dev["ops"] or dev["modules"], lo, hi)))
        for dev in ev["devices"].values()]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)
