#!/usr/bin/env python3
"""Readings for a cell's correctness limit, on the chip at the cell's size.

    python3 bench/control.py --workload <name> --seeds 11,12,... \\
        --control-seeds 11,12,13 [--seconds 30]

For each seed, in one process: draw the weights, serve the cell's traffic
at its own load through the timed path, and take the run's seeded sample of
served greedy requests. It prints the program's reading, the widest gap by
which a served token lies below the reference's best logit, and, for the
control seeds, the control's: the reference itself in int8 (weights per
output channel, activations per row), the precision below the bf16 that the
configuration states, reading at each position the gap of the token it puts
first. The limit lies between the program's largest reading and the
control's smallest.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

from run import setup_paths  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    setup_paths()
    import harness
    spec = harness.load_spec(args.workload)
    harness.prepare_device(spec, True)
    ctl = {int(s) for s in args.control_seeds.split(",") if s}
    target = int(spec.cell["check"]["served_tokens"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        w, params = harness.served_weights(spec, seed)
        sv = harness.serve(spec, w, params, seed, args.seconds, False,
                           time.perf_counter())
        del params
        sample = harness.sample_checked(sv.run, seed, target)
        row = {"seed": seed, "requests": len(sample)}
        if sample:
            t0 = time.perf_counter()
            g = harness.logit_gaps(spec, w, sample)
            row["reference_s"] = time.perf_counter() - t0
            row["tokens"] = int(sum(x.size for x in g))
            g = np.concatenate(g)
            row.update(program_max=float(g.max()), program_mean=float(g.mean()),
                       program_mismatch=float((g > 0).mean()))
            if seed in ctl:
                c = np.concatenate(
                    harness.logit_gaps(spec, w, sample, quant="int8"))
                row.update(control_max=float(c.max()),
                           control_mean=float(c.mean()),
                           control_mismatch=float((c > 0).mean()))
        print(json.dumps(row), flush=True)
        del w, sv
    return 0


if __name__ == "__main__":
    sys.exit(main())
