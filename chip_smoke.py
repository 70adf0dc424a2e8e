#!/usr/bin/env python3
"""Bring-up smoke run of the main path on TPU chips.

    python3 chip_smoke.py [--seed N]              # one chip, every phase
    python3 chip_smoke.py --chips 4 [--seed N]    # 2x2 mesh plan vs local

Drives DirectLiNGAM, the serving engine and VarLiNGAM through their
public entry points at the paper's sizes (``configs/lingam_workloads``)
on data simulated from ``--seed`` (``repro.data.simulate``), and checks
every result; two plans are compared on a causal chain of that size
(see :func:`chain`). Each phase prints one line: wall seconds, compile seconds
(JAX's own trace/lower/compile events), the device's
``peak_bytes_in_use`` so far, and its check figures. The last line is
``{"ok": true, "device": {...}}``; a process without a TPU, or any failed
phase, exits nonzero before printing it. Everything runs in this one
process: a chip belongs to one process at a time.

This is a bring-up check, not a benchmark: its seconds include
compilation and host data generation is outside them.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

#: Largest off-diagonal |Pallas - reference| moment difference accepted
#: at 65,164 samples: fp32 sums of ~6.5e4 terms of magnitude <= 1 in two
#: different orders differ by ~1e-6; 1e-4 leaves room for the
#: transcendental implementations.
MOMENT_TOL = 1e-4
#: Adjacency agreement between plans that chose the same order: the
#: finish is the same computation, so only reduction order differs.
ADJ_RTOL = 1e-3

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_compile_s = [0.0]


def _on_event(event, duration_secs, **_):
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration_secs


class PhaseFailed(AssertionError):
    pass


def check(ok, msg):
    if not ok:
        raise PhaseFailed(msg)


def phase(name, fn, *args):
    """Run one phase; print its line. A failure propagates (and ends the
    run): no phase is caught and carried on from."""
    from repro.obs import metrics

    metrics.reset()
    c0, t0 = _compile_s[0], time.perf_counter()
    figures = fn(*args)
    seconds = time.perf_counter() - t0
    peak = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.local_devices()
    )
    print(
        f"phase {name}: seconds={seconds:.3f} "
        f"compile_s={_compile_s[0] - c0:.3f} peak_bytes_in_use={peak} "
        + " ".join(f"{k}={v}" for k, v in figures.items()),
        flush=True,
    )
    return figures


def check_fit(res, d, what):
    """A DirectLiNGAM result: the order is a permutation and the
    adjacency finite and strictly lower-triangular in that order."""
    order = np.asarray(res.order)
    b = np.asarray(res.adjacency)
    check(sorted(order.tolist()) == list(range(d)),
          f"{what}: order is not a permutation of {d}")
    check(np.isfinite(b).all(), f"{what}: non-finite adjacency")
    upper = np.abs(np.triu(b[np.ix_(order, order)]))
    check(upper.max() == 0.0,
          f"{what}: adjacency not strictly lower-triangular in its order "
          f"(max |B| on/above the diagonal {upper.max()})")
    return order, b


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def device_phase(chips):
    devs = jax.devices()
    print(f"jax {jax.__version__}; devices: {devs}", flush=True)
    check(devs[0].platform == "tpu",
          f"platform {devs[0].platform!r}: this check runs on a TPU only")
    check(len(devs) >= chips, f"{len(devs)} devices, {chips} needed")
    return {"kind": repr(devs[0].device_kind), "count": len(devs)}


@functools.partial(jax.jit, static_argnames="rows")
def reference_moments(x_std, c, rows=8):
    """The ``ref`` oracle's moments, one block of ``rows`` i-rows at a
    time so the (rows, d, m) residuals fit on the device."""
    from repro.kernels import ref

    m, d = x_std.shape
    d_pad = -(-d // rows) * rows
    xt = x_std.T
    inv = jax.lax.rsqrt(jnp.maximum(1.0 - c * c, ref.EPS))
    pad = ((0, d_pad - d), (0, 0))

    def block(args):
        xi, ci, invi = args  # (rows, m), (rows, d), (rows, d)
        u = (xi[:, None, :] - ci[:, :, None] * xt[None]) * invi[:, :, None]
        logcosh, uexp = ref.nonlinear_terms(u)
        return jnp.mean(logcosh, axis=-1), jnp.mean(uexp, axis=-1)

    m1, m2 = jax.lax.map(block, (
        jnp.pad(xt, pad).reshape(d_pad // rows, rows, m),
        jnp.pad(c, pad).reshape(d_pad // rows, rows, d),
        jnp.pad(inv, pad).reshape(d_pad // rows, rows, d),
    ))
    return m1.reshape(d_pad, d)[:d], m2.reshape(d_pad, d)[:d]


def kernel_phase(x):
    """One ordering step's moments at full width: the default-resolved
    backend against the fp32 ``ref`` oracle at highest precision."""
    from repro.kernels import ops
    from repro.kernels.tune import registry

    backend = registry.default_backend()
    interpret = registry.resolve_interpret(None)
    check(backend == "pallas" and interpret is False,
          f"default kernel resolved to backend={backend!r} "
          f"interpret={interpret}, not the compiled Pallas kernel")
    with jax.default_matmul_precision("highest"):
        x_std = ops.standardize(jnp.asarray(x))
        c = ops.correlation(x_std)
        r1, r2 = reference_moments(x_std, c)
    m1, m2 = ops.pairwise_moments(x_std, c)
    off = ~np.eye(x.shape[1], dtype=bool)
    diff = max(
        float(np.abs(np.asarray(a) - np.asarray(b))[off].max())
        for a, b in ((m1, r1), (m2, r2))
    )
    check(np.isfinite(diff) and diff <= MOMENT_TOL,
          f"Pallas moments differ from the reference by {diff}")
    return {"backend": backend, "interpret": interpret,
            "max_abs_diff": f"{diff:.3e}", "tol": MOMENT_TOL}


def gene_fit_phase(x):
    from repro.core import api

    res = api.fit_fn(jnp.asarray(x), api.FitConfig(compaction="staged"))
    jax.block_until_ready(res)
    check_fit(res, x.shape[1], "gene-964 staged fit")
    return {"compaction": "staged",
            "n_edges": int((np.asarray(res.adjacency) != 0).sum())}


def compare_plans(ref, test, true_order, what):
    """Two plans' fits of the same data: the same order, adjacency
    within ``ADJ_RTOL``."""
    d = len(true_order)
    o1, b1 = check_fit(ref, d, f"{what}: reference fit")
    o2, b2 = check_fit(test, d, f"{what}: fit under test")
    n_disagree = int((o1 != o2).sum())
    check(n_disagree == 0,
          f"{what}: orders differ at {n_disagree} of {d} positions")
    diff = float(np.abs(b1 - b2).max())
    tol = ADJ_RTOL * max(1.0, float(np.abs(b1).max()))
    check(diff <= tol, f"{what}: adjacencies differ by {diff} > {tol}")
    return {"order_n_disagree": n_disagree,
            "recovers_true_order": bool(np.array_equal(o1, true_order)),
            "adj_max_abs_diff": f"{diff:.3e}", "adj_tol": f"{tol:.3e}"}


def backends_phase(x, true_order):
    from repro.core import api

    xj = jnp.asarray(x)
    blocked = api.fit_fn(
        xj, api.FitConfig(compaction="staged", backend="blocked")
    )
    default = api.fit_fn(xj, api.FitConfig(compaction="staged"))
    return compare_plans(blocked, default, true_order, "backends")


def serving_phase(requests, stream_rows, d):
    from repro.core import api
    from repro.infer import query as query_lib
    from repro.serve.engine import CausalDiscoveryEngine, FitRequest
    from repro.stream.session import StreamConfig

    eng = CausalDiscoveryEngine()
    done = eng.run([FitRequest(data=x) for x in requests])
    for i, r in enumerate(done):
        check_fit(r.result, d, f"fit request {i}")
        check(np.isfinite(r.result.resid_var).all(),
              f"fit request {i}: non-finite resid_var")

    chunk = 256
    sid = eng.open_stream(StreamConfig(
        d=d, chunk=chunk, window_chunks=8, lags=1, refit_every=4,
        fit=api.FitConfig(compaction="staged", moment_chunk=chunk),
    ))
    deltas = []
    for k in range(len(stream_rows) // chunk):
        deltas += eng.post_chunk(sid, stream_rows[k * chunk:(k + 1) * chunk])
        errs = list(eng.last_flush_errors)
        check(not errs, "flush errors: " + "; ".join(e.summary() for e in errs))
    deltas += eng.flush_streams()
    check(not list(eng.last_flush_errors), "flush errors on the final flush")
    session = eng.stream_session(sid)
    check(session.n_refits >= 2, f"only {session.n_refits} stream refits")
    check_fit(session.last_fit.result, d, "stream refit")

    effect = query_lib.EffectQuery(graph=sid)
    rca = query_lib.RCAQuery(graph=sid, rows=stream_rows[-8:], target=0)
    eng.query([effect, rca])
    check(effect.effects.shape == (d, d) and np.isfinite(effect.effects).all(),
          "effect query: bad answer")
    check(rca.result.scores.shape == (8, d)
          and np.isfinite(rca.result.scores).all()
          and np.isfinite(rca.result.contributions).all(),
          "RCA query: bad answer")
    return {"fit_requests": len(done), "stream_chunks": session.n_chunks,
            "stream_refits": session.n_refits, "deltas": len(deltas),
            "flush_errors": 0, "queries": 2}


def var_phase(series):
    from repro.core import VarLiNGAM

    model = VarLiNGAM(lags=1).fit(series)
    res = model.result_
    check_fit(res, series.shape[1], "VarLiNGAM B0")
    check(all(np.isfinite(t).all() for t in model.adjacency_matrices_),
          "VarLiNGAM: non-finite lag matrices")
    return {"lags": 1, "n_matrices": len(model.adjacency_matrices_)}


def mesh_phase(x, true_order):
    """The 2x2 mesh plan against the local plan on one of its devices."""
    from repro.core import api

    part = api.Partition(mesh=(("data", 2), ("model", 2)),
                         sample_axes=("data",), pair_axis="model")
    local = api.fit_fn(
        jax.device_put(jnp.asarray(x), jax.devices()[0]),
        api.FitConfig(compaction="staged"),
    )
    jax.block_until_ready(local)
    mesh = api.fit_fn(
        jnp.asarray(x), api.FitConfig(compaction="staged", partition=part)
    )
    n_devices = len(mesh.adjacency.sharding.device_set)
    check(n_devices == 4, f"mesh output spans {n_devices} devices, not 4")
    return {"mesh": "data2xmodel2", "output_devices": n_devices,
            **compare_plans(local, mesh, true_order, "mesh vs local")}


def chain(m, d, seed):
    """A causal chain x_0 -> ... -> x_{d-1} (``simulate_lingam`` with one
    variable per layer) and its true order. Plans are compared on it
    because it has exactly one valid order: every ordering step's
    winner leads by ~1e-4 in score, far above the ~1e-7 by which two
    plans' fp32 moments differ. On graphs with many mutually
    independent variables (the stocks and gene simulators) the lead
    falls to ~1e-9 and such plans may pick different, equally valid
    orders."""
    from repro.data import simulate

    gt = simulate.simulate_lingam(m=m, d=d, n_layers=d, edge_prob=1.0,
                                  seed=seed)
    return gt.data, np.asarray(gt.order)


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2 mesh plan and its local "
                         "comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    phase("device", device_phase, args.chips)
    jax.monitoring.register_event_duration_secs_listener(_on_event)

    from repro import obs
    from repro.configs.lingam_workloads import WORKLOADS
    from repro.data import simulate
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    # Telemetry records host spans; it stages nothing into the compiled
    # programs.
    obs.enable()
    gene = WORKLOADS["lingam-gene-964"]
    stocks = WORKLOADS["varlingam-stocks-487"]
    if args.chips == 4:
        phase("mesh_chain_gene964", mesh_phase,
              *chain(gene.m, gene.d, args.seed))
    else:
        gene_x, _, _ = simulate.simulate_gene_perturb(
            m=gene.m, d=gene.d, seed=args.seed
        )
        phase("kernel_gene964", kernel_phase, gene_x)
        phase("fit_gene964", gene_fit_phase, gene_x)
        del gene_x
        phase("backends_chain_stocks487", backends_phase,
              *chain(stocks.m, stocks.d, args.seed))
        series, _, _ = simulate.simulate_var_stocks(
            m=stocks.m, d=stocks.d, seed=args.seed
        )
        requests = [
            simulate.simulate_var_stocks(m=stocks.m, d=stocks.d,
                                         seed=args.seed + k)[0]
            for k in range(1, 5)
        ]
        stream, _, _ = simulate.simulate_var_stocks(
            m=16 * 256, d=stocks.d, seed=args.seed + 5
        )
        phase("serving_stocks487", serving_phase, requests, stream,
              stocks.d)
        phase("varlingam_stocks487", var_phase, series)
    devs = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }}))


if __name__ == "__main__":
    main()
