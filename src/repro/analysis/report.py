"""Roofline attribution report: where a fit's seconds and FLOPs go.

Splits a full DirectLiNGAM fit into its ordering / pruning / solve
stages and reports, per stage and per kernel variant: wall seconds,
FLOPs, bytes, achieved GFLOP/s, and fraction of the device roofline
(:mod:`repro.obs.profile` supplies cost capture and the device-peaks
registry). Two modes::

  PYTHONPATH=src python -m repro.analysis.report --roofline
      # live: run a small profiled fit and print the attribution tables

  PYTHONPATH=src python -m repro.analysis.report --roofline --smoke
      # CI: render + validate the committed BENCH_profile.json artifact
      # (no jit work); nonzero exit on a missing/broken artifact

The live path is also the engine of ``benchmarks/bench_profile.py``
(artifact ``BENCH_profile.json``), so the committed rows and this CLI
always agree on schema.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

_REPO_ROOT = Path(__file__).resolve().parents[3]

#: Stage rows must carry these keys — ``--smoke`` validates the
#: committed artifact against them (regress.py tracks best_s/gflops).
STAGE_KEYS = ("stage", "best_s", "flops", "bytes",
              "gflops_per_s", "roofline_frac", "bound")


def _stage_fns():
    """Jitted per-stage programs sharing the full fit's arithmetic."""
    import jax
    import jax.numpy as jnp

    from repro.core import api, pruning

    @functools.partial(jax.jit, static_argnames=("config",))
    def ordering_fn(x, config):
        return api._order_for_config(x.astype(jnp.float32), config)

    @functools.partial(jax.jit, static_argnames=("config",))
    def pruning_fn(x, order, config):
        return pruning.estimate_adjacency(
            x.astype(jnp.float32), order,
            method=config.prune_method, threshold=config.prune_threshold,
            **config.prune_kwargs_dict,
        )

    @jax.jit
    def solve_fn(x, b):
        x = x.astype(jnp.float32)
        xc = x - jnp.mean(x, axis=0, keepdims=True)
        resid = xc - xc @ b.T
        return jnp.mean(resid * resid, axis=0)

    return ordering_fn, pruning_fn, solve_fn


def _record_row(label: str, rec) -> Dict[str, Any]:
    from repro.obs import profile

    row = {"stage": label, **rec.row(profile.device_peaks())}
    row.pop("op", None)
    row.pop("config", None)
    return row


def live_attribution(
    m: int = 512, d: int = 16, *,
    backend: Optional[str] = None, compaction: str = "staged",
    repeats: int = 2, include_pallas: bool = True,
) -> Dict[str, Any]:
    """Run one profiled fit; return {rows, kernels, device}.

    Stages re-execute the fit's three phases as separate jitted
    programs (ordering scan, adjacency solve, residual diagnostics)
    plus the fused ``full_fit`` — so per-stage seconds are directly
    comparable and their sum bounds the fused time from above.
    ``repeats`` timed calls per stage; best-of is reported.
    """
    import dataclasses

    import numpy as np

    from repro.core import api
    from repro.obs import profile

    profile.enable()
    cfg = api.FitConfig(backend=backend, compaction=compaction)
    rng = np.random.default_rng(0)
    # Upper-triangular SEM: x_j = sum_{k<j} w x_k + laplace noise.
    w = np.triu(rng.uniform(0.3, 0.8, (d, d)), 1) * \
        (rng.random((d, d)) < 0.4)
    e = rng.laplace(size=(m, d)).astype(np.float32)
    x = np.linalg.solve(np.eye(d) - w.T, e.T).T.astype(np.float32)

    ordering_fn, pruning_fn, solve_fn = _stage_fns()

    stages: List[Dict[str, Any]] = []
    for _ in range(repeats):
        order = profile.call(ordering_fn, x, cfg,
                             op="report.ordering", shape=x.shape, config=cfg)
        b = profile.call(pruning_fn, x, order, cfg,
                         op="report.pruning", shape=x.shape, config=cfg)
        profile.call(solve_fn, x, b,
                     op="report.solve", shape=x.shape)
        api.fit_fn(x, cfg)  # routes through profile as op="core.fit"
    for label, op, key_cfg in (("ordering", "report.ordering", cfg),
                               ("pruning", "report.pruning", cfg),
                               ("solve", "report.solve", None)):
        rec = profile.get(op, x.shape, key_cfg)
        if rec is not None:
            stages.append(_record_row(label, rec))
    full = profile.get("core.fit", x.shape, cfg)
    if full is not None:
        stages.append(_record_row("full_fit", full))

    kernels = kernel_variant_rows(
        m, d, repeats=repeats, include_pallas=include_pallas
    )
    return {
        "m": m, "d": d,
        "rows": stages,
        "kernels": kernels,
        "device": dataclasses.asdict(profile.device_peaks()),
    }


def kernel_variant_rows(
    m: int, d: int, *, repeats: int = 2, include_pallas: bool = True,
) -> List[Dict[str, Any]]:
    """Per-backend utilization at one (m, d): each
    ``pairwise_moments`` backend timed through the profiled path (the
    Pallas variant runs interpreted on cpu — slow but measured)."""
    import numpy as np

    from repro.kernels import ops
    from repro.obs import profile

    profile.enable()
    rng = np.random.default_rng(1)
    x = rng.normal(size=(m, d)).astype(np.float32)
    x_std = (x - x.mean(0)) / x.std(0)
    c = (x_std.T @ x_std) / m

    backends = ["blocked"] + (["pallas"] if include_pallas else [])
    rows: List[Dict[str, Any]] = []
    for backend in backends:
        op = f"report.kernel.{backend}"
        for _ in range(repeats):
            profile.call(
                ops.pairwise_moments, x_std, c,
                op=op, shape=(m, d), backend=backend,
            )
        rec = profile.get(op, (m, d))
        if rec is None:
            continue
        row = _record_row(backend, rec)
        row["variant"] = row.pop("stage")
        row["backend"] = backend
        # The analytic model next to the measured numbers: how far the
        # documented flop/byte budget sits from XLA's own count.
        model = profile.analytic_cost("pairwise_moments", (m, d))
        if model is not None:
            row["model_flops"] = model["flops"]
            row["model_intensity"] = model["intensity"]
        rows.append(row)
    return rows


def _fmt_table(rows: List[Dict[str, Any]], label_key: str) -> str:
    head = (f"{'stage':<22} {'seconds':>10} {'GFLOP':>10} {'GB':>10} "
            f"{'GFLOP/s':>10} {'%roof':>7} {'bound':>8}")
    lines = [head, "-" * len(head)]
    for r in rows:
        lines.append(
            f"{str(r.get(label_key, '?')):<22} "
            f"{r.get('best_s', 0.0):>10.4g} "
            f"{r.get('flops', 0.0) / 1e9:>10.4g} "
            f"{r.get('bytes', 0.0) / 1e9:>10.4g} "
            f"{r.get('gflops_per_s', 0.0):>10.3g} "
            f"{100.0 * r.get('roofline_frac', 0.0):>6.2f}% "
            f"{str(r.get('bound', '-')):>8}"
        )
    return "\n".join(lines)


def render(payload: Dict[str, Any]) -> str:
    dev = payload.get("device", {})
    out = [
        f"roofline attribution — m={payload.get('m')} d={payload.get('d')} "
        f"device={dev.get('name', '?')} "
        f"(peak {dev.get('flops_per_s', 0) / 1e9:.0f} GFLOP/s, "
        f"{dev.get('hbm_bw', 0) / 1e9:.0f} GB/s)",
        "",
        "per-stage attribution:",
        _fmt_table(payload.get("rows", []), "stage"),
        "",
        "per-kernel-variant utilization (pairwise_moments):",
        _fmt_table(payload.get("kernels", []), "variant"),
    ]
    return "\n".join(out)


def smoke(repo_root: Path = _REPO_ROOT) -> int:
    """Validate + render the committed BENCH_profile.json (CI mode)."""
    p = repo_root / "BENCH_profile.json"
    if not p.exists():
        print(f"error: {p} missing", file=sys.stderr)
        return 1
    try:
        payload = json.loads(p.read_text())
    except ValueError as e:
        print(f"error: {p} unparsable: {e}", file=sys.stderr)
        return 1
    rows = payload.get("rows", [])
    kernels = payload.get("kernels", [])
    broken = 0
    for row in rows:
        missing = [k for k in STAGE_KEYS if k not in row]
        if missing:
            print(f"error: stage row {row.get('stage', '?')!r} missing "
                  f"{missing}", file=sys.stderr)
            broken += 1
    if not rows:
        print("error: BENCH_profile.json has no stage rows", file=sys.stderr)
        broken += 1
    if not kernels:
        print("error: BENCH_profile.json has no kernel rows",
              file=sys.stderr)
        broken += 1
    print(render(payload))
    print(f"\nsmoke: {len(rows)} stage rows, {len(kernels)} kernel rows, "
          f"{'OK' if not broken else 'BROKEN'}")
    return 1 if broken else 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Per-stage / per-kernel roofline attribution report.")
    ap.add_argument("--roofline", action="store_true",
                    help="produce the attribution report (the only mode)")
    ap.add_argument("--smoke", action="store_true",
                    help="validate + render committed BENCH_profile.json "
                         "instead of running a live fit")
    ap.add_argument("--m", type=int, default=512)
    ap.add_argument("--d", type=int, default=16)
    ap.add_argument("--backend", type=str, default=None,
                    help="force the fit backend (default: registry pick)")
    ap.add_argument("--no-pallas", action="store_true",
                    help="skip the (interpreted-on-cpu) Pallas variant row")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the payload as JSON")
    args = ap.parse_args(argv)

    if not args.roofline:
        ap.error("nothing to do: pass --roofline")
    if args.smoke:
        return smoke()

    payload = live_attribution(
        args.m, args.d, backend=args.backend,
        include_pallas=not args.no_pallas,
    )
    print(render(payload))
    if args.out:
        Path(args.out).write_text(json.dumps(payload, indent=1))
        print(f"\nwrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
