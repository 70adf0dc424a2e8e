"""Observability subsystem: spans, metrics, compile log, regression CLI.

Covers the telemetry PR's contracts:

  * spans nest by host call stack, carry attributes, record errors,
    and render as a tree; disabled telemetry returns a shared no-op.
  * metrics survive concurrent serving sessions (exact counter totals
    under a thread storm) and export snapshot / Prometheus text.
  * jit-safety: instrumented and uninstrumented fits are bit-identical
    with equal compile counts, and enabling telemetry triggers no
    retrace of warm programs.
  * enabled-telemetry overhead stays under 2% of a bootstrap-style
    batched fit (a primitive-count budget, not a wall-clock A/B).
  * the compile log is queryable by op / signature and powers the
    public one-compile-per-bucket pins.
  * ``analysis/regress.py`` flags out-of-tolerance slowdowns (nonzero
    exit), respects the tolerance band and absolute floor, and its
    ``--smoke`` mode validates committed artifacts.
"""

import json
import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from repro import obs
from repro.analysis import regress
from repro.core import api, batched
from repro.data.simulate import simulate_lingam
from repro.obs import compile_log, metrics, trace


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    obs.reset_all()
    yield
    obs.disable()
    obs.reset_all()


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_spans_nest_and_carry_attrs():
    obs.enable()
    with obs.span("outer", d=4) as outer:
        with obs.span("inner", step=1):
            pass
        with obs.span("inner", step=2) as s:
            s.set(variant="blocked")
    (root,) = obs.roots()
    assert root is outer
    assert root.attrs == {"d": 4}
    assert [c.name for c in root.children] == ["inner", "inner"]
    assert root.children[1].attrs == {"step": 2, "variant": "blocked"}
    assert root.duration_s >= max(c.duration_s for c in root.children)
    tree = obs.format_tree()
    assert "outer" in tree and "{step=2, variant=blocked}" in tree


def test_span_records_error_and_unwinds_stack():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("nope")
    (root,) = obs.roots()
    assert root.attrs["error"] == "ValueError"
    with obs.span("after"):
        pass
    assert [r.name for r in obs.roots()] == ["boom", "after"]  # not nested


def test_disabled_telemetry_is_noop():
    assert not obs.enabled()
    s = obs.span("x", d=1)
    assert s is obs.span("y")  # the shared no-op singleton
    with s:
        metrics.inc("c")
        metrics.observe("h", 1.0)
        metrics.gauge("g", 2.0)
    assert obs.roots() == []
    snap = metrics.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
    assert obs.format_tree() == "(no spans recorded)"


def test_spans_feed_latency_histograms():
    obs.enable()
    with obs.span("stage"):
        pass
    h = metrics.snapshot()["histograms"]["span.stage_s"]
    assert h["count"] == 1 and h["max"] >= 0.0


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_metric_series_keyed_by_labels():
    obs.enable()
    metrics.inc("q", 2, kind="effects")
    metrics.inc("q", 3, kind="rca")
    metrics.inc("q", kind="effects")
    metrics.gauge("stale", 4, sid="s0")
    for v in (0.1, 0.2, 0.3, 0.4):
        metrics.observe("lat_s", v, d=8)
    snap = metrics.snapshot()
    assert snap["counters"]['q{kind="effects"}'] == 3.0
    assert snap["counters"]['q{kind="rca"}'] == 3.0
    assert snap["gauges"]['stale{sid="s0"}'] == 4.0
    h = snap["histograms"]['lat_s{d="8"}']
    assert h["count"] == 4 and h["max"] == 0.4
    assert abs(h["sum"] - 1.0) < 1e-12
    assert 0.1 <= h["p50"] <= h["p95"] <= h["p99"] <= 0.4


def test_metrics_stable_under_concurrent_sessions():
    """A thread storm of counter/histogram/span traffic loses nothing:
    counter totals are exact and snapshots taken mid-storm never see
    torn state."""
    obs.enable()
    n_threads, n_iter = 8, 300
    errs = []

    def session(tid):
        try:
            for i in range(n_iter):
                with obs.span("sess.step", tid=tid):
                    metrics.inc("sess.requests", sid=f"s{tid}")
                    metrics.observe("sess.lat_s", i * 1e-6)
                if i % 50 == 0:
                    snap = metrics.snapshot()
                    assert set(snap) == {"counters", "gauges", "histograms"}
        except Exception as e:  # pragma: no cover - failure path
            errs.append(e)

    threads = [
        threading.Thread(target=session, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    snap = metrics.snapshot()
    per_sid = [snap["counters"][f'sess.requests{{sid="s{t}"}}']
               for t in range(n_threads)]
    assert per_sid == [float(n_iter)] * n_threads
    assert snap["histograms"]["sess.lat_s"]["count"] == n_threads * n_iter
    # Every thread's roots landed (each thread has its own span stack).
    assert sum(r.name == "sess.step" for r in obs.roots()) == min(
        n_threads * n_iter, 256
    )


def test_prometheus_text_format():
    obs.enable()
    metrics.inc("serve.requests", 5, kind="fit")
    metrics.gauge("stream.staleness_chunks", 2, sid="s0")
    metrics.observe("serve.flush_s", 0.25)
    text = metrics.to_prometheus_text()
    assert 'serve_requests_total{kind="fit"} 5.0' in text
    assert 'stream_staleness_chunks{sid="s0"} 2.0' in text
    assert "serve_flush_s_count 1" in text
    assert "serve_flush_s_p99 0.25" in text
    assert text.endswith("\n")


def test_prometheus_help_type_headers_once_per_family():
    obs.enable()
    metrics.inc("serve.requests", 2, kind="fit")
    metrics.inc("serve.requests", 3, kind="flush")
    metrics.gauge("stream.cadence_chunks", 8, sid="s0")
    text = metrics.to_prometheus_text()
    assert text.count("# TYPE serve_requests_total counter") == 1
    assert text.count("# HELP serve_requests_total ") == 1
    assert text.count("# TYPE stream_cadence_chunks gauge") == 1
    lines = text.splitlines()
    # Headers precede their family's sample lines.
    t = lines.index("# TYPE serve_requests_total counter")
    assert lines[t + 1].startswith("serve_requests_total{")
    assert lines[t + 2].startswith("serve_requests_total{")


def test_prometheus_escapes_label_values():
    obs.enable()
    metrics.inc("serve.flush_errors", 1,
                error='shape ("x", 2)\nmismatch \\ bad')
    text = metrics.to_prometheus_text()
    assert (
        r'serve_flush_errors_total{error="shape (\"x\", 2)\n'
        r'mismatch \\ bad"} 1.0' in text
    )
    assert "\nmismatch" not in text  # no raw newline inside a sample


# ---------------------------------------------------------------------------
# BoundedRing
# ---------------------------------------------------------------------------


def test_bounded_ring_caps_and_counts_drops():
    ring = obs.BoundedRing(3)
    for i in range(5):
        ring.append(i)
    assert list(ring) == [2, 3, 4]  # oldest evicted first
    assert len(ring) == 3
    assert ring.dropped == 2
    assert ring[0] == 2 and ring[-1] == 4
    assert ring[1:] == [3, 4]
    assert bool(ring)
    ring.clear()
    assert not ring and ring.dropped == 0


def test_bounded_ring_drain_empties_oldest_first():
    ring = obs.BoundedRing(8)
    ring.extend("abc")
    assert ring.drain() == ["a", "b", "c"]
    assert ring.drain() == []
    assert not ring


def test_bounded_ring_rejects_nonpositive_capacity():
    with pytest.raises(ValueError):
        obs.BoundedRing(0)


# ---------------------------------------------------------------------------
# jit-safety: bit-identical results, equal compile counts, bounded cost
# ---------------------------------------------------------------------------

_CFG = api.FitConfig(backend="blocked", compaction="staged")


def test_instrumented_fit_bit_identical_and_no_retrace():
    gt = simulate_lingam(m=400, d=7, seed=42)
    x = jnp.asarray(gt.data)

    r_off = api.fit_fn(x, _CFG)
    n_off = compile_log.total()
    obs.enable()
    r_on = api.fit_fn(x, _CFG)  # warm program: no retrace under telemetry
    assert compile_log.total() == n_off
    np.testing.assert_array_equal(
        np.asarray(r_off.order), np.asarray(r_on.order)
    )
    np.testing.assert_array_equal(
        np.asarray(r_off.adjacency), np.asarray(r_on.adjacency)
    )
    np.testing.assert_array_equal(
        np.asarray(r_off.resid_var), np.asarray(r_on.resid_var)
    )


def test_instrumented_trace_compiles_and_matches_uninstrumented():
    """Fresh shapes traced with telemetry ON and OFF compile the same
    number of programs and agree bit-for-bit (spans/metrics stage no
    ops into the trace)."""
    gt = simulate_lingam(m=352, d=6, seed=7)

    obs.enable()
    n0 = compile_log.total()
    r_on = api.fit_fn(jnp.asarray(gt.data), _CFG)
    compiles_on = compile_log.total() - n0
    tree_on = obs.format_tree()
    assert compiles_on >= 1
    assert "[trace]" in tree_on  # stage spans ran at trace time

    obs.disable()
    obs.reset_all()
    gt2 = simulate_lingam(m=353, d=6, seed=7)  # new shape -> fresh trace
    n1 = compile_log.total()
    api.fit_fn(jnp.asarray(gt2.data), _CFG)
    compiles_off = compile_log.total() - n1
    assert compiles_off == compiles_on

    # Identical input through the telemetry-on-traced program vs the
    # telemetry-off-traced one: same compiled math, same bits.
    r_off = api.fit_fn(jnp.asarray(gt.data), _CFG)
    np.testing.assert_array_equal(
        np.asarray(r_on.adjacency), np.asarray(r_off.adjacency)
    )


def test_enabled_overhead_under_two_percent(monkeypatch):
    """Bound enabled-telemetry cost on the serving path by counting, not
    timing: one warm batched fit through ``CausalDiscoveryEngine.run``
    issues fewer than 25 span/metric primitives (serve.run + fit_bucket
    spans, two observes, a counter) — the budget that keeps telemetry
    under 2% of a bootstrap-style fit — and retraces nothing. (A
    wall-clock ratio on a shared CPU is noise, not a bound.)"""
    from repro.serve.engine import CausalDiscoveryEngine, FitRequest

    gt = simulate_lingam(m=500, d=8, seed=3)
    eng = CausalDiscoveryEngine(_CFG, batch_size=4)

    def requests():
        return [FitRequest(data=gt.data) for _ in range(4)]

    eng.run(requests())  # warm: compile with telemetry off
    calls = []

    def counted(mod, name):
        fn = getattr(mod, name)

        def wrapped(*a, **k):
            calls.append(name)
            return fn(*a, **k)

        monkeypatch.setattr(mod, name, wrapped)

    counted(obs, "span")
    for name in ("inc", "gauge", "observe"):
        counted(metrics, name)
    obs.enable()
    n0 = compile_log.total()
    eng.run(requests())
    assert compile_log.total() == n0
    assert calls.count("span") >= 2  # the serving spans are counted
    assert len(calls) < 25, calls


# ---------------------------------------------------------------------------
# compile log
# ---------------------------------------------------------------------------


def test_compile_log_keys_and_queries():
    compile_log.record("op.a", shape=(64, 5), config=_CFG, note="first")
    compile_log.record("op.a", shape=(64, 5), config=_CFG)
    compile_log.record("op.a", shape=(128, 5), config=_CFG)
    compile_log.record("op.b")
    key = ("op.a", (64, 5), compile_log.config_hash(_CFG))
    assert compile_log.counts("op.a")[key] == 2
    assert compile_log.total("op.a") == 3
    assert compile_log.by_op() == {"op.a": 3, "op.b": 1}
    assert [e["op"] for e in compile_log.events("op.b")] == ["op.b"]
    assert compile_log.events("op.a")[0]["note"] == "first"
    snap = compile_log.snapshot()
    assert snap["by_op"]["op.a"] == 3
    assert any(k.startswith("op.a:[64, 5]") for k in snap["by_signature"])
    # Distinct configs hash to distinct signatures.
    other = api.FitConfig(backend="blocked", prune_method="adaptive")
    assert compile_log.config_hash(other) != compile_log.config_hash(_CFG)
    assert compile_log.config_hash(None) == "-"


def test_compile_log_always_on_and_feeds_metrics_when_enabled():
    assert not obs.enabled()
    compile_log.record("op.silent", shape=(2,))
    assert compile_log.total("op.silent") == 1  # recorded while disabled
    assert metrics.snapshot()["counters"] == {}
    obs.enable()
    compile_log.record("op.loud")
    assert metrics.snapshot()["counters"]['compiles{op="op.loud"}'] == 1.0


# ---------------------------------------------------------------------------
# regression tracker
# ---------------------------------------------------------------------------


def _fake_artifact(scale=1.0):
    return {
        "bench": "bootstrap",
        "quick": True,
        "timestamp": "2026-01-01T00:00:00",
        "rows": [{
            "cell": "m2000.d16", "m": 2000, "d": 16,
            "loop_s": 1.0 * scale, "vmap_s": 0.1 * scale,
            "vmap_fits_per_s": 100.0 / scale, "speedup": 10.0,
            "edge_prob_agree": 0.99,  # not a perf metric
        }],
    }


def test_collect_metrics_directions_and_labels():
    got = regress.collect_metrics(_fake_artifact())
    assert got["rows[cell=m2000.d16,m=2000,d=16].loop_s"] == ("lower", 1.0)
    assert got["rows[cell=m2000.d16,m=2000,d=16].vmap_fits_per_s"] == (
        "higher", 100.0
    )
    assert not any(m.endswith("edge_prob_agree") for m in got)
    # Time units normalize to seconds (ms/us suffixes).
    us = regress.collect_metrics({"rows": [{"op": "k", "tuned": {"us": 2.0}}]})
    assert us["rows[op=k].tuned.us"] == ("lower", 2e-6)


def test_compare_tolerance_band_and_floor():
    base = regress.collect_metrics(_fake_artifact(1.0))
    # 50% slower: beyond tol and the absolute floor -> regression.
    worse = {d.metric: d for d in regress.compare(
        base, regress.collect_metrics(_fake_artifact(1.5)),
        tol=0.25, min_abs=0.005,
    )}
    assert worse["rows[cell=m2000.d16,m=2000,d=16].loop_s"].status == \
        "REGRESSED"
    assert worse["rows[cell=m2000.d16,m=2000,d=16].vmap_fits_per_s"].status \
        == "REGRESSED"  # rate fell below the band
    # 10% slower: inside the band -> ok.
    ok = regress.compare(
        base, regress.collect_metrics(_fake_artifact(1.1)),
        tol=0.25, min_abs=0.005,
    )
    assert all(d.status == "ok" for d in ok)
    # Microsecond-scale jitter: relatively huge, absolutely tiny -> the
    # floor keeps it from failing a build.
    tiny_b = {"m.t_s": ("lower", 1e-4)}
    tiny_c = {"m.t_s": ("lower", 3e-4)}
    (d,) = regress.compare(tiny_b, tiny_c, tol=0.25, min_abs=0.005)
    assert d.status == "ok"
    (d,) = regress.compare(tiny_b, tiny_c, tol=0.25, min_abs=0.0)
    assert d.status == "REGRESSED"


def test_compare_flags_new_and_missing_metrics():
    base = {"a_s": ("lower", 1.0)}
    cur = {"b_s": ("lower", 1.0)}
    by = {d.metric: d.status for d in regress.compare(
        base, cur, tol=0.25, min_abs=0.005
    )}
    assert by == {"a_s": "missing", "b_s": "new"}


def test_regress_cli_exit_codes(tmp_path, capsys):
    basedir, curdir = tmp_path / "base", tmp_path / "cur"
    basedir.mkdir(), curdir.mkdir()
    (basedir / "BENCH_bootstrap.json").write_text(
        json.dumps(_fake_artifact(1.0))
    )
    (curdir / "BENCH_bootstrap.json").write_text(
        json.dumps(_fake_artifact(2.0))
    )
    rc = regress.main([
        "--baseline-dir", str(basedir), "--current-dir", str(curdir),
        "--only", "bootstrap",
    ])
    assert rc == 1
    assert "REGRESSED" in capsys.readouterr().out
    # Same artifacts within tolerance -> success.
    (curdir / "BENCH_bootstrap.json").write_text(
        json.dumps(_fake_artifact(1.05))
    )
    assert regress.main([
        "--baseline-dir", str(basedir), "--current-dir", str(curdir),
        "--only", "bootstrap",
    ]) == 0
    # Smoke mode self-compares the baselines.
    assert regress.main([
        "--baseline-dir", str(basedir), "--smoke", "--only", "bootstrap",
    ]) == 0
    # No baselines at all is an error.
    assert regress.main(["--baseline-dir", str(curdir / "nope")]) == 2


def test_regress_smoke_on_committed_artifacts():
    """The repo's own BENCH_*.json artifacts parse and yield metrics."""
    rc = regress.main(["--smoke"])
    assert rc == 0


def test_provenance_shape():
    prov = obs.provenance(repo_root=str(regress._REPO_ROOT))
    for k in ("timestamp", "jax_version", "device_kind", "git_sha"):
        assert k in prov
    assert prov["git_sha"] not in ("", None)
