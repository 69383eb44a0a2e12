#!/usr/bin/env python3
"""Find a cell's knee on the chip: one process, one set of weights, a fresh
engine at each offered rate.

    python3 bench/sweep.py --workload <name> --rates 0.2,0.4,0.8 \\
        [--seconds 30] [--drain 15] [--seed 1] [--slo-scale 3]

The first rate is the unloaded one. The TTFT and ITL limits are
``--slo-scale`` times its p90 TTFT and p95 ITL (DistServe's SLO scale,
arXiv:2401.09670); at each rate the run prints its tails, its output rate,
the share of the window's requests that met both limits, and how many were
left unanswered at the window's close (a growing backlog). The knee is the
highest rate at which 90% met both limits; the cell runs at about 0.8 of
it. Requests still open ``--drain`` seconds after the window count as
misses. Results go to standard output as one JSON line per rate.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import setup_paths  # noqa: E402


def summary(run, ttft_limit_ms=None, itl_limit_ms=None) -> dict:
    from harness import percentile
    due = run.due
    ttft = [run.ttft(r) * 1e3 for r in due]
    gaps = [(b - a) * 1e3 for r in run.requests
            for a, b in zip(r.times, r.times[1:]) if run.in_window(b)]
    out = {"due": len(due), "ttft_p50_ms": percentile(ttft, 50),
           "ttft_mean_ms": sum(ttft) / max(len(ttft), 1),
           "ttft_p90_ms": percentile(ttft, 90),
           "itl_p50_ms": percentile(gaps, 50),
           "itl_p95_ms": percentile(gaps, 95),
           "output_tok_s": sum(run.in_window(t) for r in run.requests
                               for t in r.times) / run.seconds,
           "open_at_close": sum(r.end is None or r.end >= run.window[1]
                                for r in due),
           "preempted": sum(r.req.preemptions > 0 for r in due),
           "tick_wall_ms": percentile([(s.end - s.start) * 1e3
                                       for s in run.window_steps], 50),
           "lanes_per_tick": percentile([len(s.decode)
                                         for s in run.window_steps], 50),
           "kv_pool": run.pool}
    if ttft_limit_ms is not None:
        met = 0
        for r, t in zip(due, ttft):
            g = [b - a for a, b in zip(r.times, r.times[1:])]
            mean_gap = 1e3 * sum(g) / len(g) if g else 0.0
            met += (r.reason in ("length", "stop") and t <= ttft_limit_ms
                    and mean_gap <= itl_limit_ms)
        out["attainment"] = met / max(len(due), 1)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--drain", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--slo-scale", type=float, default=3.0)
    args = ap.parse_args(argv)
    setup_paths()
    import harness
    spec = harness.load_spec(args.workload)
    spec.cell = dict(spec.cell, drain_s=args.drain)
    harness.prepare_device(spec, True)
    w, params = harness.served_weights(spec, args.seed)
    limits = None
    for rate in [float(x) for x in args.rates.split(",")]:
        sv = harness.serve(spec, w, params, args.seed, args.seconds, False,
                           time.perf_counter(), rate=rate)
        if limits is None:
            s0 = summary(sv.run)
            limits = (args.slo_scale * s0["ttft_p90_ms"],
                      args.slo_scale * s0["itl_p95_ms"])
        row = {"rate": rate, **summary(sv.run, *limits),
               "ttft_limit_ms": limits[0], "itl_limit_ms": limits[1]}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
