"""From a JAX profiler trace to the numbers the per-layer metrics read.

``extract`` turns the profiler's ``.xplane.pb`` into plain lists: per
device, each program execution and each operation as ``[name, start_ns,
duration_ns]``, and the host's annotated spans. ``reduce`` turns those
lists into busy and idle time, per-program device time, and the breakdown.
The two halves are split so that ``reduce`` can be checked on a small
recorded trace without a chip.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict

_SUFFIX = re.compile(r"\(\d+\)$")


def _device_planes(pd):
    return [p for p in pd.planes
            if p.name.startswith("/device:") and "CPU" not in p.name]


def extract(trace_dir: str) -> dict:
    """Device and host events of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no trace written under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    devices = {}
    for plane in _device_planes(pd):
        lines = {ln.name: ln for ln in plane.lines}
        mods = lines.get("XLA Modules")
        ops = lines.get("XLA Ops")
        if mods is None and ops is None:
            continue
        devices[plane.name] = {
            "modules": [[_SUFFIX.sub("", e.name), e.start_ns, e.duration_ns]
                        for e in (mods.events if mods else [])],
            # an op's event name is its whole HLO instruction; the part
            # before " = " names it
            "ops": [[e.name.split(" = ")[0], e.start_ns, e.duration_ns]
                    for e in (ops.events if ops else [])]}
    if not devices:
        raise ValueError("no device plane with XLA Modules or XLA Ops: " + "; ".join(
            f"{p.name}: {[ln.name for ln in p.lines]}" for p in pd.planes))
    host = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            evs = [[e.name, e.start_ns, e.duration_ns] for e in ln.events]
            if any(e[0].startswith("bench.") for e in evs):
                host.extend(evs)          # the driving thread only
    return {"devices": devices, "host": host}


def _union(intervals, lo, hi):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    merged = []
    for s, e in sorted((max(s, lo), min(s + d, hi)) for _, s, d in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _module_of(ops, modules):
    """Each op's enclosing program execution's name, by time."""
    mods = sorted((s, s + d, n) for n, s, d in modules)
    out, j = [], 0
    for name, s, d in sorted(ops, key=lambda o: o[1]):
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        owner = mods[j][2] if j < len(mods) and mods[j][0] <= s else "?"
        out.append((f"{owner}/{name}", d))
    return out


def _host_path(host, t):
    """The name of the innermost ``engine.*`` span that contains time
    ``t``: what the serving engine was doing. Outside the engine's spans,
    the innermost span of any kind; outside every span, 'host idle'."""
    inside = [n for _, _, n in sorted((s, -d, n) for n, s, d in host
                                      if s <= t < s + d)]
    engine = [n for n in inside if n.startswith("engine.")]
    return (engine or inside or ["host idle"])[-1]


def reduce(events: dict, window: tuple) -> dict:
    """Busy time, per-program device time and the breakdown over
    ``window`` = (start_ns, end_ns), averaged over the devices traced."""
    lo, hi = window
    devices = list(events["devices"].values())
    if not devices:
        raise ValueError("the trace holds no device events")
    busy, per_prog = [], defaultdict(list)
    op_time = defaultdict(float)
    gaps = []
    for i, dev in enumerate(devices):
        spans = dev["ops"] or dev["modules"]
        merged = _union(spans, lo, hi)
        busy.append(sum(e - s for s, e in merged))
        for name, s, d in dev["modules"]:
            if lo <= s < hi:
                per_prog[name].append(d * 1e-9)
        for name, d in _module_of(dev["ops"], dev["modules"]):
            op_time[name] += d * 1e-9
        if i == 0:
            edges = [lo] + [x for se in merged for x in se] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((_host_path(events["host"], (s + e) / 2),
                                 (e - s) * 1e-9))
    window_s = (hi - lo) * 1e-9
    busy_s = sum(busy) / len(busy) * 1e-9
    return {
        "window_s": window_s, "busy_s": busy_s,
        "programs": dict(per_prog),
        "breakdown": {
            "device_ops": sorted(op_time.items(), key=lambda x: -x[1])[:10],
            "idle_gaps": sorted(gaps, key=lambda x: -x[1])[:10]}}


def window_of(events: dict, name: str = "bench.window") -> tuple:
    """The traced window, as the host span ``name`` marks it."""
    for n, s, d in events["host"]:
        if n == name:
            return s, s + d
    raise ValueError(f"no {name!r} span in the trace")
