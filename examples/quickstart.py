"""Quickstart: causal discovery with AcceleratedLiNGAM on TPU/CPU.

    PYTHONPATH=src python examples/quickstart.py [--telemetry] [--profile]

Simulates data from a known layered DAG (paper §3.1 protocol), runs the
parallel DirectLiNGAM, verifies it against the sequential reference,
prints the recovered adjacency — then *uses* the graph: total-effect
queries, a do-intervention, and root-cause attribution of an anomalous
sample (the full discovery -> query path).

With ``--telemetry`` the run also drives the serving engine (a fit
micro-batch, a streaming session through refit flushes, and a causal
query) with the observability layer on (:mod:`repro.obs`), then prints
the span tree, the metrics snapshot, and the compile-event log —
covering ordering -> pruning -> serve flush -> query.

With ``--profile`` it runs the performance-accounting layer
(:mod:`repro.obs.profile`): a profiled fit inside a correlated
host+device trace window, the stage-attribution table (seconds, FLOPs,
%-of-roofline per stage and kernel variant), and the captured cost
records — writing the device trace (Perfetto) next to the host span
trace under the ``--profile-out`` directory.
"""

import argparse

import numpy as np

from repro.baselines.sequential_lingam import causal_order_sequential
from repro.core import DirectLiNGAM, VarLiNGAM, api, batched
from repro.core.bootstrap import bootstrap_lingam
from repro.data.simulate import simulate_do, simulate_lingam, simulate_var_stocks
from repro.infer import effects, intervene, rca
from repro.launch.compile_cache import enable_compile_cache


def main():
    print("=== DirectLiNGAM (paper Algorithm 1, parallel) ===")
    gt = simulate_lingam(m=5_000, d=10, seed=0)
    model = DirectLiNGAM(backend="blocked", prune_threshold=0.1).fit(gt.data)
    print("causal order :", model.causal_order_)
    print("sequential   :", causal_order_sequential(gt.data))
    agree = np.array_equal(
        model.causal_order_, causal_order_sequential(gt.data)
    )
    print(f"parallel == sequential: {agree}")

    est = np.abs(model.adjacency_) > 0.1
    true = gt.adjacency != 0
    print(f"edges: true={true.sum()} recovered={est.sum()} "
          f"correct={np.sum(est & true)}")

    print("\n=== Pallas kernel backend (interpret mode on CPU) ===")
    model_k = DirectLiNGAM(backend="pallas").fit(gt.data)
    print("pallas order :", model_k.causal_order_)
    print("orders agree :", np.array_equal(model.causal_order_,
                                           model_k.causal_order_))

    print("\n=== Functional core: pure fit_fn + vmap-batched bootstrap ===")
    import jax.numpy as jnp

    res = api.fit_fn(jnp.asarray(gt.data), api.FitConfig(backend="blocked"))
    print("fit_fn order  :", np.asarray(res.order))
    print("resid_var[:4] :", np.asarray(res.resid_var)[:4].round(3))

    boot = bootstrap_lingam(
        gt.data, n_sampling=10, threshold=0.1, seed=0, strategy="vmap"
    )
    print("stable edges (P>=0.8):",
          [(i, j, p) for i, j, p, _ in boot.stable_edges(0.8)][:5])

    # fit_many: one compiled program fitting an ensemble of datasets.
    xs = jnp.stack([
        jnp.asarray(simulate_lingam(m=2_000, d=10, seed=s).data)
        for s in range(4)
    ])
    ens = batched.fit_many(xs, api.FitConfig(compaction="staged"))
    print("fit_many orders (4 datasets):")
    print(np.asarray(ens.order))

    print("\n=== VarLiNGAM (paper §3.2) ===")
    x, b0, m1 = simulate_var_stocks(m=2_000, d=20, edge_prob=0.1, seed=1)
    var_model = VarLiNGAM(lags=1, prune_threshold=0.05).fit(x)
    th0 = var_model.adjacency_matrices_[0]
    tp = np.sum((np.abs(th0) > 0.05) & (b0 != 0))
    print(f"instantaneous edges: true={np.sum(b0 != 0)} "
          f"recovered-correct={tp}")

    print("\n=== Causal queries on the fitted graph (repro.infer) ===")
    # Total effects: (I - B)^-1 by triangular solve in causal order.
    t = np.asarray(effects.total_effects(model.result_))
    off = np.abs(t) * (1 - np.eye(t.shape[0]))
    i, j = np.unravel_index(np.argmax(off), t.shape)
    print(f"strongest total effect: x{j} -> x{i} = {t[i, j]:+.3f} "
          f"(direct {model.adjacency_[i, j]:+.3f})")

    # Intervention: predicted do(x_j = +2) mean vs interventional sampling.
    mu_do, _ = intervene.interventional_moments(
        model.result_, {int(j): 2.0},
        mean=gt.data.mean(axis=0), cov=np.cov(gt.data.T, ddof=0),
    )
    mc = simulate_do(gt.adjacency, {int(j): 2.0}, m=20_000, seed=0)
    print(f"do(x{j}=2): predicted E[x{i}]={mu_do[i]:+.3f}  "
          f"Monte-Carlo={mc[:, i].mean():+.3f}")

    # Root-cause attribution: inject an anomaly into x_j's noise term
    # and ask the graph who broke.
    x_anom = gt.data[:1].copy()
    x_anom[0] += 4.0 * t[:, j]  # shift j's noise by +4, propagated
    report = rca.attribute(
        model.result_, x_anom, mean=gt.data.mean(axis=0), target=int(i)
    )
    print(f"RCA: implicated root = x{report.root[0]} (injected x{j}); "
          f"ranking {report.ranking(top_k=3)}")


def telemetry_demo(out_dir=None):
    """Drive ordering -> pruning -> serve flush -> query
    with telemetry on; print the span tree + metrics + compile log.

    ``out_dir`` additionally writes ``metrics_snapshot.json``. For the
    spans on a timeline with the device ops, run under
    ``jax.profiler.trace`` (``--profile``).
    """
    import json
    import os

    from repro import obs
    from repro.infer import query as query_lib
    from repro.serve.engine import CausalDiscoveryEngine, FitRequest
    from repro.stream.session import StreamConfig

    obs.enable()
    obs.reset_all()
    rng = np.random.default_rng(0)

    print("\n=== Telemetry: serving engine under observation ===")
    eng = CausalDiscoveryEngine(batch_size=4)
    eng.run([
        FitRequest(data=rng.normal(size=(256, 8)).astype(np.float32))
        for _ in range(3)
    ])
    sid = eng.open_stream(
        StreamConfig(d=6, chunk=32, window_chunks=4, refit_every=1)
    )
    for _ in range(7):
        eng.post_chunk(sid, rng.normal(size=(32, 6)).astype(np.float32))
    eng.flush_streams()
    answered = eng.query([
        query_lib.EffectQuery(graph=sid),
        query_lib.InterventionQuery(graph=sid, do={0: 1.5}),
    ])
    print(f"stream {sid}: {eng.stream_session(sid).n_refits} refits, "
          f"{len(answered)} queries answered, "
          f"{len(eng.last_flush_errors)} flush errors")

    print("\n--- span tree (spans tagged [trace] ran at trace time) ---")
    print(obs.format_tree())
    print("--- metrics snapshot ---")
    print(json.dumps(obs.metrics.snapshot(), indent=1, sort_keys=True))
    print("--- compile events (op -> compiles) ---")
    for op, n in sorted(obs.compile_log.by_op().items()):
        print(f"  {op}: {n}")

    if out_dir is not None:
        os.makedirs(out_dir, exist_ok=True)
        metrics_path = os.path.join(out_dir, "metrics_snapshot.json")
        with open(metrics_path, "w") as f:
            json.dump(obs.metrics.snapshot(), f, indent=1, sort_keys=True)
        print(f"wrote {metrics_path}")


def profile_demo(out_dir=None):
    """Profiled fit + stage attribution + correlated device trace.

    ``out_dir`` receives a ``device_trace/`` directory (the
    ``jax.profiler`` trace: host spans as annotations and device ops on
    one clock, each op under its ``lingam.<stage>`` scope) and
    ``profile_snapshot.json`` (the captured cost records + device
    peaks).
    """
    import json
    import os

    import jax

    from repro import obs
    from repro.analysis import report
    from repro.obs import profile

    obs.enable()
    profile.enable()
    obs.reset_all()

    print("\n=== Profiling: cost capture + roofline attribution ===")
    if out_dir is not None:
        os.makedirs(out_dir, exist_ok=True)
        with jax.profiler.trace(os.path.join(out_dir, "device_trace")):
            payload = report.live_attribution(m=512, d=16, repeats=2)
    else:
        payload = report.live_attribution(m=512, d=16, repeats=2)
    print(report.render(payload))

    print("\n--- captured cost records ---")
    for rec in profile.records():
        print(f"  {rec.op} shape={rec.shape} flops={rec.flops:.3g} "
              f"bytes={rec.bytes_accessed:.3g} temp={rec.temp_bytes} "
              f"calls={rec.calls} best={rec.best_s * 1e3:.2f}ms")

    if out_dir is not None:
        snap_path = os.path.join(out_dir, "profile_snapshot.json")
        with open(snap_path, "w") as f:
            json.dump(profile.snapshot(), f, indent=1)
        print(f"\nwrote {snap_path} and "
              f"{os.path.join(out_dir, 'device_trace')}/ (open the trace "
              f"in ui.perfetto.dev: host spans and device ops on one "
              f"timeline)")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--telemetry", action="store_true",
                    help="run the serving/streaming demo with repro.obs "
                         "enabled and print span tree + metrics")
    ap.add_argument("--telemetry-out", type=str, default="telemetry_out",
                    help="directory for --telemetry artifacts "
                         "(metrics snapshot)")
    ap.add_argument("--profile", action="store_true",
                    help="run the profiled fit: stage-attribution table, "
                         "cost records, correlated host+device trace")
    ap.add_argument("--profile-out", type=str, default="profile_out",
                    help="directory for --profile artifacts "
                         "(profiler trace, cost snapshot)")
    args = ap.parse_args()
    enable_compile_cache()
    main()
    if args.telemetry:
        telemetry_demo(out_dir=args.telemetry_out)
    if args.profile:
        profile_demo(out_dir=args.profile_out)
