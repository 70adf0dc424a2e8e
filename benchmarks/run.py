"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints ``name,metric=value,...`` CSV lines per benchmark and mirrors
every benchmark's results to a repo-root ``BENCH_<name>.json`` file
— the machine-readable perf-trajectory artifacts CI and future sessions
diff.
``--out`` optionally also writes one aggregate JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import (  # noqa: E402
    bench_bootstrap,
    bench_drift,
    bench_equivalence,
    bench_gene,
    bench_infer,
    bench_notears,
    bench_profile,
    bench_sharded,
    bench_speedup,
    bench_stocks,
    bench_stream,
)

BENCHES = {
    "speedup": bench_speedup.run,          # paper Fig. 2
    "equivalence": bench_equivalence.run,  # paper Fig. 3
    "notears": bench_notears.run,          # paper §3.1
    "gene": bench_gene.run,                # paper Table 1
    "stocks": bench_stocks.run,            # paper Fig. 4 / Table 2
    "bootstrap": bench_bootstrap.run,      # loop vs vmap-batched engine
    "sharded": bench_sharded.run,          # mesh-plan sweep vs 1-dev oracle
    "stream": bench_stream.run,            # rolling-window vs from-scratch
    "infer": bench_infer.run,              # batched queries vs per-query loop
    "drift": bench_drift.run,              # drift detection + refit savings
    "profile": bench_profile.run,          # cost accounting + roofline rows
}

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--only", type=str, default=None)
    ap.add_argument("--profile", action="store_true",
                    help="enable repro.obs.profile for every bench and "
                         "stamp artifact rows with captured cost fields "
                         "(flops/bytes/utilization)")
    ap.add_argument("--out", type=str, default=None,
                    help="optional aggregate JSON (per-bench artifacts "
                         "always land as repo-root BENCH_*.json)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache  # noqa: E402,PLC0415
    from repro.obs import profile as obs_profile  # noqa: E402,PLC0415

    enable_compile_cache()
    if args.profile:
        obs_profile.enable()

    results = {}
    profiles = {}
    for name, fn in BENCHES.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        print(f"=== bench:{name} ===")
        obs_profile.reset()
        try:
            results[name] = fn(quick=not args.full)
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            results[name] = {"error": str(e)}
        if args.profile:
            profiles[name] = obs_profile.snapshot()
        print(f"=== bench:{name} done in {time.time()-t0:.1f}s ===\n")

    def default(o):
        import numpy as np

        if isinstance(o, (np.integer,)):
            return int(o)
        if isinstance(o, (np.floating,)):
            return float(o)
        raise TypeError(type(o))

    from repro import obs  # noqa: E402,PLC0415

    prov = obs.provenance(repo_root=_REPO_ROOT)

    def stamp_rows(payload: dict, snap: dict) -> dict:
        """Join captured cost records onto a payload's row dicts.

        A row matches a record on its ``op`` field (and, when both carry
        one, its ``shape``); matched rows gain flops/bytes/temp_bytes
        and the utilization columns. The full record table also lands
        under ``payload["profile"]`` so unjoined costs aren't dropped.
        """
        records = snap.get("records", [])
        by_op = {}
        for rec in records:
            by_op.setdefault(rec["op"], []).append(rec)

        def stamp(node):
            if isinstance(node, list):
                for item in node:
                    stamp(item)
            elif isinstance(node, dict):
                cands = by_op.get(node.get("op"), [])
                hit = None
                for rec in cands:
                    if "shape" in node and list(node["shape"]) != rec["shape"]:
                        continue
                    hit = rec
                    break
                if hit is not None:
                    for k in ("flops", "bytes", "temp_bytes",
                              "gflops_per_s", "gbytes_per_s",
                              "roofline_frac", "bound"):
                        if k in hit and k not in node:
                            node[k] = hit[k]
                for v in node.values():
                    stamp(v)

        stamp(payload.get("rows"))
        payload["profile"] = snap
        return payload

    def write_artifact(stem: str, payload: dict) -> None:
        """Mirror one benchmark's results to BENCH_<stem>.json at the
        repo root — the machine-readable perf-trajectory artifacts CI
        and future sessions diff. Each artifact is stamped with run
        provenance (device kind, jax version, git sha, timestamp) so a
        regression report can say *what* produced the numbers."""
        out = os.path.join(_REPO_ROOT, f"BENCH_{stem}.json")
        with open(out, "w") as f:
            json.dump(
                {
                    "bench": stem,
                    "quick": not args.full,
                    "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
                    "provenance": prov,
                    **payload,
                },
                f, indent=1, default=default,
            )
        print(f"wrote {out}")

    for name, res in results.items():
        if isinstance(res, dict) and "error" in res:
            continue
        payload = res if isinstance(res, dict) else {"rows": res}
        if args.profile and name in profiles:
            payload = stamp_rows(dict(payload), profiles[name])
        write_artifact(name, payload)

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=default)
        print(f"wrote {args.out}")

    failed = [n for n, r in results.items()
              if isinstance(r, dict) and "error" in r]
    if failed:
        sys.exit(f"benches failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
