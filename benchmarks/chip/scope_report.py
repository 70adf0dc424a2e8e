#!/usr/bin/env python3
"""Run one benchmark cell traced, as ``run.py --trace 1`` does, and add the
per-stage reduction of its trace (``xplane_scopes.py``).

    python3 benchmarks/chip/scope_report.py --workload gene964.fit \
        --seed 7 --seconds 30

The last line of standard output is ``run.py``'s result object with two
more keys: ``stages``, milliseconds per graph of each stage group
(``xplane_scopes.stage_ms``), and ``scopes``, the reduction itself
(``scope_s``, ``idle_in_span_s``, ``unscoped_paths``) with the seconds its
decoding took (``decode_s``). A run on a program without the stage names
reads None for every stage.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run
import trace_reduce
import xplane_scopes


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    scoped = {}
    reduce_dir = trace_reduce.reduce_dir

    def reduce_both(log_dir, devices=1):
        t0 = time.perf_counter()
        scoped.update(xplane_scopes.reduce_file(
            trace_reduce.find_xplane(log_dir), devices))
        scoped["decode_s"] = time.perf_counter() - t0
        return reduce_dir(log_dir, devices)

    trace_reduce.reduce_dir = reduce_both
    try:
        result = run.run(args.workload, args.seed, args.seconds, True)
    except run.NoDevice as e:
        print(f"scope_report: {e}", file=sys.stderr)
        return run.EXIT_NO_DEVICE
    result["stages"] = xplane_scopes.stage_ms(scoped, result["attempted"])
    result["scopes"] = scoped
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
