#!/usr/bin/env python3
"""Readings of the program and of its control, over many seeds, in one
process: the readings each check limit is set from.

    python3 benchmarks/chip/control.py --workload gene964.fit \
        --seeds 11,12,13 [--control-seeds 11,12,13] [--graphs 2]

For each seed the cell's job is set up as a run sets it up (without the
warm-up graph), drives ``--graphs`` graphs of the program through the
timed path, and is checked against the plain reference. For the seeds in
``--control-seeds`` the control is read as well: the reference computed
in bfloat16 put in the program's place, at the same ordering steps and on
the same rows. One JSON line per seed, with every number the job's check
reads, whether its cell's limits file lists it or not; the benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def readings(workload, seeds, control_seeds, graphs, *, require_tpu=True,
             loaded=None):
    bench, cell, config, traffic, limits = loaded or run.load_cell(workload)
    run.check_device(cell["chips"], require_tpu)
    run.enable_compile_cache()
    job_mod = run.load_module(
        os.path.join(HERE, "jobs", traffic["job"] + ".py"),
        "job_" + traffic["job"])
    for seed in seeds:
        job = job_mod.Job(config, dict(traffic, check_graphs=graphs), seed,
                          limits)
        job.setup(warm=False)
        for _ in range(graphs):
            job.graph()
        job.release()
        job.check()
        row = {"seed": seed, "program": job.readings}
        if seed in control_seeds:
            job.check(control=True)
            row["control"] = job.readings
        yield row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--graphs", type=int, default=1)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    try:
        for row in readings(args.workload, seeds, control, args.graphs):
            print(json.dumps(row), flush=True)
    except run.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return run.EXIT_NO_DEVICE
    return 0


if __name__ == "__main__":
    sys.exit(main())
