"""Graph-health monitoring: drift detection, alerts, adaptive cadence.

The drift-monitoring PR's contracts:

  * **moments only** — scoring a chunk re-reads zero rows: the monitor
    consumes the exact :class:`MomentState` the rolling window already
    produced for the slide (pinned by counting ``stats.from_chunk``
    calls per post).
  * **statistics** — on data generated *by* the served model the
    standardized chunk statistics are ~unit-scale; each simulated break
    kind (noise-scale, weight-shift, edge-flip) is detected on the
    broken variable, and a stationary stream raises no alarms
    (hypothesis calibration test).
  * **adaptive cadence** — stable refits coast (interval doubles up to
    ``coast_max``), an alert makes the session due immediately and
    resets the interval.
  * **serving surface** — alerts flow through ``poll_alerts`` (drained
    exactly once), ride the triggering ``GraphDelta``, and land in
    ``obs.metrics``; a drift-triggered refit that *fails* keeps the
    alert pending and isolates the failure exactly like any other
    flush error.
  * **batched scoring** — ``score_chunks_many`` equals per-monitor
    ``update`` and compiles once per (d, lags) shape family.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import api
from repro.data.simulate import BREAK_KINDS, simulate_var_breaks, simulate_var_stocks
from repro.obs import compile_log
from repro.serve.engine import CausalDiscoveryEngine
from repro.stream import (
    MonitorConfig,
    StreamConfig,
    StreamSession,
    monitor as monitor_lib,
    stats,
    window,
)

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - minimal envs
    HAVE_HYPOTHESIS = False

_CFG = api.FitConfig(backend="blocked", compaction="staged")


def _cfg(d, chunk, wc, **kw):
    kw.setdefault("monitor", MonitorConfig())
    return StreamConfig(
        d=d, chunk=chunk, window_chunks=wc, lags=1, fit=_CFG, **kw
    )


def _drive(session, series, *, refit=True):
    """Post a series chunk by chunk, refitting whenever due."""
    chunk = session.config.chunk
    n = (series.shape[0] // chunk) * chunk
    for start in range(0, n, chunk):
        if session.post(series[start:start + chunk]) and refit:
            session.refit_now()


# ----------------------------------------------------------------------
# Moments only: zero row re-reads per monitored chunk
# ----------------------------------------------------------------------


def test_monitoring_adds_zero_row_passes(monkeypatch):
    """Monitoring reads only moment statistics: a monitored session
    makes exactly as many chunk-moment passes (``stats.from_chunk``,
    the only entry that touches rows) as an unmonitored one over the
    same stream — the monitor scores the slide's own summary, it never
    re-reads rows or re-derives chunk moments."""
    d, chunk, wc = 6, 64, 3
    x = simulate_var_stocks(m=chunk * (wc + 6) + 4, d=d, seed=5)[0]
    calls = {"n": 0}
    orig = stats.from_chunk

    def counting_from_chunk(rows):
        calls["n"] += 1
        return orig(rows)

    monkeypatch.setattr(window.stats, "from_chunk", counting_from_chunk)

    def drive(config):
        calls["n"] = 0
        s = StreamSession("s", config)
        for k in range(wc + 5):
            if s.post(x[k * chunk:(k + 1) * chunk]):
                s.refit_now()
        return s, calls["n"]

    plain = StreamConfig(
        d=d, chunk=chunk, window_chunks=wc, lags=1, fit=_CFG
    )
    _, n_plain = drive(plain)
    s_mon, n_monitored = drive(_cfg(d, chunk, wc))
    assert s_mon.monitor.armed
    assert s_mon.monitor.n_scored > 0  # it really was scoring...
    assert n_monitored == n_plain      # ...from moments alone


# ----------------------------------------------------------------------
# The statistics: calibrated under the model, responsive to breaks
# ----------------------------------------------------------------------


def test_chunk_drift_stats_unit_scale_under_model():
    """Data generated by the served model itself yields ~unit-scale
    statistics — no systematic drift evidence."""
    rng = np.random.default_rng(0)
    d, n = 5, 400
    b0 = np.zeros((d, d), np.float32)
    b0[3, 1] = 0.7
    a = np.zeros((d, d), np.float32)       # no lag dynamics
    intercept = np.zeros(d, np.float32)
    resid_var = np.ones(d, np.float32)
    zs = []
    for _ in range(40):
        e = rng.normal(size=(n + 1, d)).astype(np.float32)
        x = np.linalg.solve(np.eye(d) - b0, e.T).T
        aug = window.lagged_rows(x, 1)
        cs = stats.from_chunk(jnp.asarray(aug))
        zs.append([np.asarray(z) for z in monitor_lib.chunk_drift_stats(
            cs.count, cs.mean, cs.m2, jnp.asarray(a),
            jnp.asarray(intercept), jnp.asarray(b0),
            jnp.asarray(resid_var),
        )])
    z = np.asarray(zs)  # (reps, 3, d)
    # Mean statistic: ~N(0, 1) -> mean near 0, std near 1.
    assert abs(z[:, 0].mean()) < 0.3
    assert 0.6 < z[:, 0].std() < 1.5
    # Variance / dependence statistics: centered near 0 at unit scale.
    assert abs(z[:, 1].mean()) < 0.5
    assert abs(z[:, 2].mean()) < 0.7


@pytest.mark.parametrize("kind", BREAK_KINDS)
def test_break_detected_on_broken_variable(kind):
    """Each simulated break kind raises an alert after the break, and
    the broken variable appears among the implicated/candidate set."""
    d, chunk, wc = 12, 100, 8
    br = simulate_var_breaks(m=6000, d=d, kind=kind, seed=0, at=3000)
    s = StreamSession(
        "s", _cfg(d, chunk, wc, refit_every=2, coast_max=32)
    )
    _drive(s, br.series)
    post = [
        a for a in s.alert_history if a.chunk_index > br.at // chunk
    ]
    assert post, f"{kind}: no post-break alert"
    implicated = {a.variable for a in post} | {
        v for a in post for v, _ in a.candidate_roots[:2]
    }
    assert br.variable in implicated
    # Alerts carry ranked root candidates and a sane score.
    assert all(a.score >= 1.0 and a.candidate_roots for a in post)


def test_noise_scale_break_classified_and_fast():
    """The cleanest break kind (pure noise re-scale) is classified as
    noise-scale and detected within a few chunks."""
    d, chunk, wc = 12, 100, 8
    br = simulate_var_breaks(
        m=6000, d=d, kind="noise_scale", seed=1, at=3000
    )
    s = StreamSession(
        "s", _cfg(d, chunk, wc, refit_every=2, coast_max=32)
    )
    _drive(s, br.series)
    post = [
        a for a in s.alert_history if a.chunk_index > br.at // chunk
    ]
    first = post[0]
    assert first.kind == "noise-scale"
    assert first.variable == br.variable
    assert first.chunk_index - br.at // chunk <= 3


def test_stationary_stream_no_alarms():
    """A stationary stream (the process the graph was fit on) raises no
    alerts across a long run — the deterministic false-alarm pin; the
    hypothesis test below widens the seed coverage."""
    d, chunk, wc = 8, 100, 6
    x = simulate_var_stocks(m=8000, d=d, seed=11)[0]
    s = StreamSession(
        "s", _cfg(d, chunk, wc, refit_every=2, coast_max=32)
    )
    _drive(s, x)
    assert len(s.alert_history) == 0
    assert s.monitor.n_scored > 30


if HAVE_HYPOTHESIS:

    @settings(max_examples=5, deadline=None)
    @given(seed=st.integers(min_value=0, max_value=10_000))
    def test_false_alarm_calibration_stationary(seed):
        """Calibration, property-tested over process seeds: stationary
        VAR streams stay below one alert-chunk in forty (at default
        thresholds nearly all seeds see zero)."""
        d, chunk, wc = 8, 100, 6
        x = simulate_var_stocks(m=5000, d=d, seed=seed)[0]
        s = StreamSession(
            "s", _cfg(d, chunk, wc, refit_every=2, coast_max=32)
        )
        _drive(s, x)
        scored = max(s.monitor.n_scored, 1)
        assert len(s.alert_history) / scored <= 1.0 / 40.0


# ----------------------------------------------------------------------
# Adaptive cadence
# ----------------------------------------------------------------------


def test_cadence_coasts_when_stable_and_resets_on_alert():
    d, chunk, wc = 12, 100, 8
    br = simulate_var_breaks(
        m=6000, d=d, kind="noise_scale", seed=2, at=3000
    )
    s = StreamSession(
        "s", _cfg(d, chunk, wc, refit_every=2, coast_max=16)
    )
    cadences = []
    n = (br.series.shape[0] // chunk) * chunk
    for start in range(0, n, chunk):
        if s.post(br.series[start:start + chunk]):
            s.refit_now()
        cadences.append((start + chunk > br.at, s.cadence))
    pre = [c for post, c in cadences if not post]
    post = [c for post, c in cadences if post]
    assert max(pre) == 16          # coasted to the cap pre-break...
    assert 2 in post               # ...and snapped back on the alert
    assert any(
        d_.triggered_by == "alert" for d_ in [s.last_delta] if d_
    ) or any(a for a in s.alert_history)


def test_alert_makes_session_due_immediately():
    """An alert overrides the coast interval: the session reports due
    on the very chunk the alert fired, not at the next cadence tick."""
    d, chunk, wc = 12, 100, 8
    br = simulate_var_breaks(
        m=6000, d=d, kind="noise_scale", seed=0, at=3000
    )
    s = StreamSession(
        "s", _cfg(d, chunk, wc, refit_every=4, coast_max=64)
    )
    n = (br.series.shape[0] // chunk) * chunk
    for start in range(0, n, chunk):
        due = s.post(br.series[start:start + chunk])
        if s.pending_alerts:
            assert due
            break
        if due:
            s.refit_now()
    else:
        pytest.fail("no alert fired")


def test_unmonitored_session_fixed_cadence_unchanged():
    """StreamConfig without a monitor behaves exactly as before: no
    monitor object, no alerts, refit_every cadence."""
    d, chunk, wc = 6, 64, 3
    s = StreamSession(
        "s", StreamConfig(d=d, chunk=chunk, window_chunks=wc, fit=_CFG,
                          refit_every=2)
    )
    assert s.monitor is None
    x = simulate_var_stocks(m=chunk * (wc + 4) + 4, d=d, seed=3)[0]
    refits = 0
    for k in range(wc + 4):
        if s.post(x[k * chunk:(k + 1) * chunk]):
            s.refit_now()
            refits += 1
    assert refits == 2  # 4 steady chunks at refit_every=2
    assert s.cadence == 2
    assert len(s.alert_history) == 0


# ----------------------------------------------------------------------
# Serving surface: poll_alerts, deltas, metrics, failure isolation
# ----------------------------------------------------------------------


def _engine_with_break(kind="noise_scale", seed=0):
    d, chunk, wc = 12, 100, 8
    br = simulate_var_breaks(m=6000, d=d, kind=kind, seed=seed, at=3000)
    eng = CausalDiscoveryEngine(batch_size=1)
    sid = eng.open_stream(
        _cfg(d, chunk, wc, refit_every=2, coast_max=32)
    )
    return eng, sid, br, chunk


def test_engine_poll_alerts_drains_once():
    eng, sid, br, chunk = _engine_with_break()
    n = (br.series.shape[0] // chunk) * chunk
    polled = []
    deltas = []
    for start in range(0, n, chunk):
        deltas += eng.post_chunk(sid, br.series[start:start + chunk])
    eng.flush_streams()
    polled += eng.poll_alerts(sid)
    assert polled, "no alerts surfaced through the serve API"
    assert eng.poll_alerts(sid) == []       # drained exactly once
    assert eng.poll_alerts() == []          # engine-wide drain agrees
    # The same alerts rode out on the triggering deltas.
    alert_deltas = [d_ for _, d_ in deltas if d_.triggered_by == "alert"]
    assert alert_deltas
    assert all(d_.alerts for d_ in alert_deltas)
    assert all(d_.drift_score > 0 for d_ in alert_deltas)
    assert any("drift" in d_.summary() for d_ in alert_deltas)
    # History retains everything polled.
    hist = list(eng.stream_session(sid).alert_history)
    assert len(hist) >= len(polled)


def test_alerts_recorded_in_obs_metrics():
    from repro import obs
    from repro.obs import metrics as obs_metrics

    obs.enable()
    try:
        obs_metrics.reset()
        eng, sid, br, chunk = _engine_with_break()
        n = (br.series.shape[0] // chunk) * chunk
        for start in range(0, n, chunk):
            eng.post_chunk(sid, br.series[start:start + chunk])
        eng.flush_streams()
        eng.poll_alerts()
        snap = obs_metrics.snapshot()
        counters = snap["counters"]
        assert any(k.startswith("monitor.alerts") for k in counters)
        assert any(
            k.startswith("monitor.alerts_by_kind") for k in counters
        )
        assert any(k.startswith("serve.alerts_polled") for k in counters)
        assert any(
            k.startswith("monitor.drift_score") for k in snap["gauges"]
        )
    finally:
        obs.disable()
        obs_metrics.reset()


def test_drift_triggered_flush_failure_keeps_alert_pending(monkeypatch):
    """A refit forced by a drift alert that then *fails* must not eat
    the alert: the FlushError is recorded, the alert stays pending (the
    session remains due for retry), and an unmonitored peer in the same
    flush is unaffected."""
    d, chunk, wc = 12, 100, 8
    br = simulate_var_breaks(
        m=6000, d=d, kind="noise_scale", seed=0, at=3000
    )
    peer_x = simulate_var_stocks(m=6000, d=d, seed=9)[0]
    eng = CausalDiscoveryEngine(batch_size=8)
    sid = eng.open_stream(_cfg(d, chunk, wc, refit_every=2, coast_max=32))
    peer = eng.open_stream(
        StreamConfig(d=d, chunk=chunk, window_chunks=wc, fit=_CFG,
                     refit_every=2)
    )
    session = eng.stream_session(sid)
    n = (br.series.shape[0] // chunk) * chunk
    for start in range(0, n, chunk):
        eng.post_chunk(sid, br.series[start:start + chunk])
        eng.post_chunk(peer, peer_x[start:start + chunk])
        if session.pending_alerts:
            break
    else:
        pytest.fail("no alert fired")
    assert session.due
    n_pending = len(session.pending_alerts)
    peer_refits = eng.stream_session(peer).n_refits

    def boom():
        raise RuntimeError("refit blew up mid-drift")

    monkeypatch.setattr(session.rolling, "prepare_refit", boom)
    eng.stream_session(peer)._chunks_since_refit = 99  # peer due too
    out = eng.flush_streams()
    # Peer refit landed; the monitored session's failure is isolated.
    assert eng.stream_session(peer).n_refits == peer_refits + 1
    assert all(s == peer for s, _ in out)
    (err,) = [e for e in eng.last_flush_errors if e.sid == sid]
    assert err.stage == "prepare"
    # The alert was NOT consumed: still pending, session still due.
    assert len(session.pending_alerts) == n_pending
    assert session.due
    # Recovery: un-poison and retry — the drift-triggered delta lands.
    monkeypatch.undo()
    out = eng.flush_streams()
    (got,) = [d_ for s, d_ in out if s == sid]
    assert got.triggered_by == "alert"
    assert len(session.pending_alerts) == 0


# ----------------------------------------------------------------------
# Batched scoring across sessions
# ----------------------------------------------------------------------


def _armed_monitor_and_chunks(seed, d=6, chunk=64, wc=3, n_extra=3):
    x = simulate_var_stocks(m=chunk * (wc + n_extra) + 4, d=d, seed=seed)[0]
    s = StreamSession("s%d" % seed, _cfg(d, chunk, wc))
    for k in range(wc):
        if s.post(x[k * chunk:(k + 1) * chunk]):
            s.refit_now()
    if not s.monitor.armed:
        s.refit_now()
    chunk_states = []
    for k in range(wc, wc + n_extra):
        rows = x[k * chunk:(k + 1) * chunk]
        aug = window.lagged_rows(
            np.concatenate([x[k * chunk - 1:k * chunk], rows]), 1
        )
        chunk_states.append(stats.from_chunk(jnp.asarray(aug)))
    return s.monitor, chunk_states


def test_score_chunks_many_matches_sequential():
    """The vmapped batch scorer leaves every monitor in the same state
    as per-monitor sequential updates (same CUSUM banks, same alerts)."""
    import copy

    mons, states = [], []
    for seed in (0, 1, 2):
        m, cs = _armed_monitor_and_chunks(seed)
        mons.append(m)
        states.append(cs[0])
    seq = [copy.deepcopy(m) for m in mons]
    out_batch = monitor_lib.score_chunks_many(
        mons, states, chunk_indices=[1, 1, 1]
    )
    out_seq = [
        m.update(s, chunk_index=1) for m, s in zip(seq, states)
    ]
    for mb, ms in zip(mons, seq):
        np.testing.assert_allclose(mb._pos, ms._pos, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(mb._neg, ms._neg, rtol=1e-5, atol=1e-5)
    assert [len(a) for a in out_batch] == [len(a) for a in out_seq]


def test_batched_scorer_compiles_once_per_shape():
    """Repeat batched scoring over the same (d, lags) family replays
    one compiled program (padding to the micro-batch bucket)."""
    mons, states = [], []
    for seed in (3, 4):
        m, cs = _armed_monitor_and_chunks(seed)
        mons.append(m)
        states.append(cs)
    monitor_lib.score_chunks_many(mons, [cs[0] for cs in states])
    n0 = compile_log.total("monitor.chunk_drift_stats")
    for j in (1, 2):
        monitor_lib.score_chunks_many(mons, [cs[j] for cs in states])
    assert compile_log.total("monitor.chunk_drift_stats") == n0


# ----------------------------------------------------------------------
# RCA attribution of drift scores
# ----------------------------------------------------------------------


def test_drift_root_candidates_discounts_descendants():
    """A drifting ancestor explains its descendant's elevated score:
    the ancestor must rank first even when the descendant's raw score
    is comparable."""
    from repro.infer import rca

    d = 4
    b0 = np.zeros((d, d), np.float32)
    b0[1, 0] = 0.9          # x0 -> x1
    b0[2, 1] = 0.9          # x1 -> x2
    order = np.array([0, 1, 2, 3])
    scores = np.array([2.0, 1.8, 0.2, 0.1], np.float32)
    ranked = rca.drift_root_candidates(b0, order, scores, top_k=3)
    assert ranked[0][0] == 0
    assert ranked[0][1] == pytest.approx(2.0)
    # An isolated variable keeps its full score.
    lone = rca.drift_root_candidates(
        b0, order, np.array([0.0, 0.0, 0.0, 3.0], np.float32), top_k=2
    )
    assert lone[0][0] == 3
