"""Streaming subsystem: moment store, rolling VarLiNGAM, serving sessions.

Covers the streaming PR's contracts:

  * ``MomentState`` algebra — merge is associative/commutative, merged
    states match the direct two-pass computation, and
    ``update_chunk`` + ``retract_chunk`` round-trips within fp32
    tolerance (hypothesis property tests where available).
  * the chunked kernel entry (``pairwise_moments_chunked``) agrees with
    the whole-slab backends, and ``FitConfig.moment_chunk`` reproduces
    the plain fit bit-for-bit.
  * ``api.fit_from_stats`` matches ``api.fit_fn`` given the dataset's
    own moments (both pruning methods), and rejects mesh partitions.
  * the parity pin: rolling-window refits (merge/retract state) equal
    the from-scratch window oracle (direct two-pass) across slides that
    exercise retraction.
  * the serving engine batches due sessions' refits through
    ``fit_many_from_stats`` with results identical to the
    single-session path, and reports sane graph deltas.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, batched
from repro.data.simulate import simulate_lingam, simulate_var_stocks
from repro.kernels import ops
from repro.obs import compile_log
from repro.serve import engine as serve_engine
from repro.serve.engine import CausalDiscoveryEngine
from repro.stream import StreamConfig, session as session_lib, stats, window

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - minimal envs
    HAVE_HYPOTHESIS = False

_CFG = api.FitConfig(backend="blocked", compaction="staged")


def _np_state(x):
    """Reference two-pass (count, mean, m2) in float64."""
    x = np.asarray(x, np.float64)
    mu = x.mean(axis=0)
    xc = x - mu
    return float(len(x)), mu, xc.T @ xc


def _assert_state_close(s, n, mu, m2, *, atol_mean=1e-4, atol_m2=None):
    scale = max(1.0, float(np.abs(m2).max()))
    atol_m2 = atol_m2 if atol_m2 is not None else 1e-4 * scale
    assert float(s.count) == pytest.approx(n)
    np.testing.assert_allclose(np.asarray(s.mean), mu, atol=atol_mean)
    np.testing.assert_allclose(np.asarray(s.m2), m2, atol=atol_m2)


def _chunks(rng, n_chunks, d, lo=20, hi=80):
    return [
        (rng.laplace(size=(int(rng.integers(lo, hi)), d))
         * rng.uniform(0.5, 3.0, d)
         + rng.uniform(-2.0, 2.0, d)).astype(np.float32)
        for _ in range(n_chunks)
    ]


# ----------------------------------------------------------------------
# MomentState algebra
# ----------------------------------------------------------------------


def test_from_chunk_matches_numpy_two_pass():
    rng = np.random.default_rng(0)
    x = _chunks(rng, 1, 6, 100, 101)[0]
    s = stats.from_chunk(jnp.asarray(x))
    _assert_state_close(s, *_np_state(x))
    cov = np.cov(x.T, ddof=0)
    np.testing.assert_allclose(
        np.asarray(stats.covariance(s)), cov, atol=1e-4
    )


def test_init_is_merge_identity():
    rng = np.random.default_rng(1)
    x = _chunks(rng, 1, 4)[0]
    s = stats.from_chunk(jnp.asarray(x))
    for merged in (stats.merge(stats.init(4), s), stats.merge(s, stats.init(4))):
        _assert_state_close(merged, *_np_state(x))


def test_retract_everything_zeroes_state():
    rng = np.random.default_rng(2)
    x = _chunks(rng, 1, 3)[0]
    s = stats.retract_chunk(stats.update_chunk(stats.init(3), x), x)
    assert float(s.count) == 0.0
    assert np.all(np.isfinite(np.asarray(s.mean)))
    assert np.all(np.isfinite(np.asarray(s.m2)))


if HAVE_HYPOTHESIS:
    _SETTINGS = dict(max_examples=20, deadline=None, derandomize=True)

    @given(seed=st.integers(0, 2**31 - 1), d=st.integers(2, 8))
    @settings(**_SETTINGS)
    def test_merge_commutative(seed, d):
        rng = np.random.default_rng(seed)
        a, b = (stats.from_chunk(jnp.asarray(c)) for c in _chunks(rng, 2, d))
        ab, ba = stats.merge(a, b), stats.merge(b, a)
        _assert_state_close(
            ba, float(ab.count), np.asarray(ab.mean), np.asarray(ab.m2)
        )

    @given(seed=st.integers(0, 2**31 - 1), d=st.integers(2, 8))
    @settings(**_SETTINGS)
    def test_merge_associative_and_matches_direct(seed, d):
        rng = np.random.default_rng(seed)
        ca, cb, cc = _chunks(rng, 3, d)
        a, b, c = (stats.from_chunk(jnp.asarray(x)) for x in (ca, cb, cc))
        left = stats.merge(stats.merge(a, b), c)
        right = stats.merge(a, stats.merge(b, c))
        n, mu, m2 = _np_state(np.concatenate([ca, cb, cc]))
        _assert_state_close(left, n, mu, m2)
        _assert_state_close(right, n, mu, m2)

    @given(seed=st.integers(0, 2**31 - 1), d=st.integers(2, 8))
    @settings(**_SETTINGS)
    def test_update_retract_roundtrip(seed, d):
        """A rolling slide (absorb b, later retract b) lands back on the
        direct two-pass state of a — within fp32 tolerance."""
        rng = np.random.default_rng(seed)
        ca, cb = _chunks(rng, 2, d)
        s = stats.update_chunk(
            stats.update_chunk(stats.init(d), ca), cb
        )
        back = stats.retract_chunk(s, cb)
        _assert_state_close(back, *_np_state(ca))


# ----------------------------------------------------------------------
# Chunked kernel entry + moment_chunk config
# ----------------------------------------------------------------------


@pytest.mark.parametrize("m,d,chunk", [(257, 7, 64), (128, 8, 128), (64, 5, 100)])
def test_chunked_moments_match_blocked(m, d, chunk):
    rng = np.random.default_rng(0)
    x = rng.laplace(size=(m, d)).astype(np.float32)
    xs = ops.standardize(jnp.asarray(x))
    c = ops.correlation(xs)
    m1a, m2a = ops.pairwise_moments(xs, c, backend="blocked")
    m1b, m2b = ops.pairwise_moments_chunked(xs, c, chunk=chunk)
    np.testing.assert_allclose(np.asarray(m1a), np.asarray(m1b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m2a), np.asarray(m2b), atol=1e-5)


def test_chunked_moments_pallas_interpret():
    rng = np.random.default_rng(3)
    x = rng.laplace(size=(128, 8)).astype(np.float32)
    xs = ops.standardize(jnp.asarray(x))
    c = ops.correlation(xs)
    m1a, m2a = ops.pairwise_moments(xs, c, backend="blocked")
    m1b, m2b = ops.pairwise_moments_chunked(
        xs, c, chunk=64, backend="pallas", interpret=True
    )
    np.testing.assert_allclose(np.asarray(m1a), np.asarray(m1b), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m2a), np.asarray(m2b), atol=1e-5)


def test_moment_chunk_config_validation():
    with pytest.raises(ValueError, match="moment_chunk"):
        api.FitConfig(backend="ref", moment_chunk=64)
    with pytest.raises(ValueError, match="moment_chunk"):
        api.FitConfig(backend="blocked", moment_chunk=0)


def test_moment_chunk_config_reproduces_plain_fit():
    gt = simulate_lingam(m=900, d=7, seed=2)
    x = jnp.asarray(gt.data)
    plain = api.fit_fn(x, _CFG)
    chunked = api.fit_fn(x, dataclasses.replace(_CFG, moment_chunk=128))
    assert np.array_equal(np.asarray(plain.order), np.asarray(chunked.order))
    np.testing.assert_allclose(
        np.asarray(plain.adjacency), np.asarray(chunked.adjacency), atol=1e-6
    )


# ----------------------------------------------------------------------
# from_stats fit path
# ----------------------------------------------------------------------


@pytest.mark.parametrize("method", ["ols", "adaptive_lasso"])
def test_fit_from_stats_matches_fit_fn(method):
    gt = simulate_lingam(m=1200, d=7, seed=0)
    x = jnp.asarray(gt.data)
    mu = jnp.mean(x, axis=0)
    xc = x - mu[None, :]
    cov = (xc.T @ xc) / x.shape[0]
    cfg = dataclasses.replace(_CFG, prune_method=method)
    full = api.fit_fn(x, cfg)
    from_stats = api.fit_from_stats(x, mu, cov, cfg)
    assert np.array_equal(
        np.asarray(full.order), np.asarray(from_stats.order)
    )
    np.testing.assert_allclose(
        np.asarray(full.adjacency), np.asarray(from_stats.adjacency),
        atol=2e-4,
    )
    # diag((I-B) cov (I-B)^T) equals the empirical residual variance.
    np.testing.assert_allclose(
        np.asarray(full.resid_var), np.asarray(from_stats.resid_var),
        rtol=1e-3, atol=1e-5,
    )


def test_fit_from_stats_rejects_partition():
    cfg = api.FitConfig(partition=api.Partition())
    with pytest.raises(ValueError, match="mesh"):
        api.fit_from_stats(
            jnp.zeros((32, 4)), jnp.zeros(4), jnp.eye(4), cfg
        )


def test_fit_many_from_stats_matches_single():
    xs, mus, covs = [], [], []
    for s in range(3):
        x = jnp.asarray(simulate_lingam(m=500, d=5, seed=s).data)
        mu = jnp.mean(x, axis=0)
        xc = x - mu[None, :]
        xs.append(x)
        mus.append(mu)
        covs.append((xc.T @ xc) / x.shape[0])
    many = batched.fit_many_from_stats(
        jnp.stack(xs), jnp.stack(mus), jnp.stack(covs), _CFG
    )
    for s in range(3):
        one = api.fit_from_stats(xs[s], mus[s], covs[s], _CFG)
        assert np.array_equal(
            np.asarray(many.order[s]), np.asarray(one.order)
        )
        np.testing.assert_allclose(
            np.asarray(many.adjacency[s]), np.asarray(one.adjacency),
            atol=1e-5,
        )


# ----------------------------------------------------------------------
# Rolling-window VarLiNGAM: the parity pin
# ----------------------------------------------------------------------


def _stock_chunks(d, chunk, n_chunks, seed=1):
    x, _, _ = simulate_var_stocks(
        m=chunk * n_chunks + 5, d=d, edge_prob=0.3, seed=seed
    )
    return [x[k * chunk:(k + 1) * chunk] for k in range(n_chunks)]


def test_rolling_matches_direct_window_oracle():
    """Rolling refit (merged + retracted moments) == from-scratch window
    refit (direct two-pass) at every slide, including post-retraction."""
    d, chunk, wc = 8, 96, 4
    roll = window.RollingVarLiNGAM(d, chunk, wc, lags=1, config=_CFG)
    n_checked = 0
    for rows in _stock_chunks(d, chunk, wc + 3):
        roll.push(rows)
        if not roll.ready:
            continue
        got = roll.refit()
        want = window.direct_window_fit(
            list(roll.ring), roll._lead_tail, lags=1, config=roll.config
        )
        assert np.array_equal(
            np.asarray(got.result.order), np.asarray(want.result.order)
        )
        np.testing.assert_allclose(
            np.asarray(got.result.adjacency),
            np.asarray(want.result.adjacency),
            atol=1e-4,
        )
        for th_got, th_want in zip(got.thetas, want.thetas):
            np.testing.assert_allclose(th_got, th_want, atol=1e-4)
        n_checked += 1
    assert n_checked == 4  # 3 of these exercised retraction


def test_rolling_var_close_to_lstsq():
    """State-derived VAR coefficients track the legacy lstsq estimate."""
    from repro.core.var_lingam import estimate_var

    d, chunk, wc = 6, 128, 4
    chunks = _stock_chunks(d, chunk, wc, seed=3)
    roll = window.RollingVarLiNGAM(d, chunk, wc, lags=1, config=_CFG)
    for rows in chunks:
        roll.push(rows)
    plan = roll.prepare_refit()
    mats, _, _ = estimate_var(np.concatenate(chunks), lags=1)
    np.testing.assert_allclose(
        plan.mats[0], np.asarray(mats[0]), atol=5e-3
    )


def test_rolling_reanchor_preserves_estimate():
    d, chunk, wc = 6, 80, 3
    chunks = _stock_chunks(d, chunk, wc + 2, seed=5)
    roll = window.RollingVarLiNGAM(d, chunk, wc, lags=1, config=_CFG)
    anchored = window.RollingVarLiNGAM(
        d, chunk, wc, lags=1, config=_CFG, reanchor_every=1
    )
    for rows in chunks:
        roll.push(rows)
        anchored.push(rows)
    a, b = roll.refit(), anchored.refit()
    assert np.array_equal(
        np.asarray(a.result.order), np.asarray(b.result.order)
    )
    np.testing.assert_allclose(
        np.asarray(a.result.adjacency), np.asarray(b.result.adjacency),
        atol=1e-4,
    )


def test_rolling_push_copies_caller_buffer():
    """A client reusing one chunk buffer across posts must not corrupt
    the ring (push copies; regression for the aliasing bug)."""
    d, chunk, wc = 6, 64, 3
    chunks = _stock_chunks(d, chunk, wc, seed=9)
    reused = window.RollingVarLiNGAM(d, chunk, wc, lags=1, config=_CFG)
    fresh = window.RollingVarLiNGAM(d, chunk, wc, lags=1, config=_CFG)
    buf = np.empty((chunk, d), np.float32)
    for rows in chunks:
        buf[:] = rows
        reused.push(buf)
        fresh.push(rows)
    a, b = reused.refit(), fresh.refit()
    assert np.array_equal(
        np.asarray(a.result.order), np.asarray(b.result.order)
    )
    np.testing.assert_array_equal(
        np.asarray(a.result.adjacency), np.asarray(b.result.adjacency)
    )


def test_rolling_validates_inputs():
    with pytest.raises(ValueError, match="chunk"):
        window.RollingVarLiNGAM(4, 1, 3, lags=1)
    with pytest.raises(ValueError, match="partition"):
        window.RollingVarLiNGAM(
            4, 32, 3,
            config=api.FitConfig(partition=api.Partition()),
        )
    roll = window.RollingVarLiNGAM(4, 32, 3)
    with pytest.raises(RuntimeError, match="not full"):
        roll.refit()
    with pytest.raises(ValueError, match="expected"):
        roll.push(np.zeros((16, 4), np.float32))


def test_rolling_window_moment_chunk_defaults_to_stream_chunk():
    r = window.RollingVarLiNGAM(d=4, chunk=64, window_chunks=3)
    assert r.config.moment_chunk == 64


def _window_moment_chunks(backend, monkeypatch):
    return [
        window.RollingVarLiNGAM(
            d=4, chunk=c, window_chunks=3,
            config=api.FitConfig(backend=backend, compaction="staged"),
        ).config.moment_chunk
        for c in (64, 100, 200, 700)
    ]


def _recorded_slabs(run, monkeypatch):
    from repro.infer import rca

    slabs = []
    pad_rows = rca._pad_rows

    def record(block, slab, axis=0):
        slabs.append(slab)
        return pad_rows(block, slab, axis)

    monkeypatch.setattr(rca, "_pad_rows", record)
    for n in (1, 100, 513):
        run(n)
    return slabs


def _rca_slabs(backend, monkeypatch):
    from repro.infer import rca

    d = 5
    graph = api.FitResult(
        order=jnp.arange(d), adjacency=jnp.zeros((d, d), jnp.float32),
        resid_var=jnp.ones((d,), jnp.float32),
    )
    return _recorded_slabs(
        lambda n: rca.attribute(graph, np.ones((n, d), np.float32)),
        monkeypatch,
    )


def _query_rca_slabs(backend, monkeypatch):
    from repro.infer import query

    d = 5
    graph = api.FitResult(
        order=jnp.arange(d), adjacency=jnp.zeros((d, d), jnp.float32),
        resid_var=jnp.ones((d,), jnp.float32),
    )
    engine = query.QueryEngine(batch_size=2)
    return _recorded_slabs(
        lambda n: engine.run([query.RCAQuery(
            graph=graph, rows=np.ones((n, d), np.float32))]),
        monkeypatch,
    )


def _monitor_buckets(backend, monkeypatch):
    from repro.kernels.tune import registry
    from repro.stream import monitor

    monkeypatch.setattr(registry, "default_backend", lambda: backend)
    return [monitor._batch_bucket(n)
            for n in (1, 3, 5, 100, 128, 129, 300, 1000)]


# Sample slabs and batch buckets that four callers outside the kernels
# size from the shapes: the values each had while a kernel block
# dispatcher chose them (per backend where they differ). The RCA slab
# (attribute and the query engine) is recorded once per slab pass over
# 1, 100 and 513 rows.
_SLAB_DECISIONS = {
    "window": (_window_moment_chunks, {
        "blocked": [64, 100, 200, 700], "pallas": [64, 100, 200, 700]}),
    "rca": (_rca_slabs, {
        "blocked": [512, 512, 512, 512], "pallas": [512, 512, 512, 512]}),
    "query": (_query_rca_slabs, {
        "blocked": [512, 512, 512, 512], "pallas": [512, 512, 512, 512]}),
    "monitor": (_monitor_buckets, {
        "blocked": [1, 3, 5, 100, 128, 129, 300, 1000],
        "pallas": [1, 4, 8, 128, 128, 256, 384, 1024]}),
}


@pytest.mark.parametrize("caller", sorted(_SLAB_DECISIONS))
def test_slab_decisions_match_parent(caller, monkeypatch):
    decide, want = _SLAB_DECISIONS[caller]
    for backend in ("blocked", "pallas"):
        assert decide(backend, monkeypatch) == want[backend], backend


# ----------------------------------------------------------------------
# Sessions + engine batching
# ----------------------------------------------------------------------


def _stream_config(d, chunk, wc, **kw):
    return StreamConfig(
        d=d, chunk=chunk, window_chunks=wc, lags=1, fit=_CFG, **kw
    )


def test_graph_delta_edge_sets():
    prev = np.array([[0.0, 0.5], [0.0, 0.0]])
    new = np.array([[0.0, 0.0], [0.8, 0.0]])
    delta = session_lib.graph_delta(prev, new, 0.1, refit_index=3)
    assert delta.refit_index == 3
    assert delta.n_edges == 1
    assert [tuple(e) for e in delta.added] == [(1, 0)]
    assert [tuple(e) for e in delta.removed] == [(0, 1)]
    assert delta.max_abs_change == pytest.approx(0.8)
    first = session_lib.graph_delta(None, new, 0.1, refit_index=0)
    assert first.n_edges == 1 and len(first.removed) == 0


def test_engine_streams_batch_and_match_single_session():
    d, chunk, wc = 8, 96, 4
    cfg = _stream_config(d, chunk, wc)
    eng = CausalDiscoveryEngine(batch_size=2)
    all_chunks = [_stock_chunks(d, chunk, wc + 2, seed=s) for s in (1, 2)]
    sids = [eng.open_stream(cfg) for _ in all_chunks]
    deltas = []
    for k in range(wc + 2):
        for sid, chunks in zip(sids, all_chunks):
            deltas += eng.post_chunk(sid, chunks[k])
    # Session 0 flushes solo at window fill (session 1 is still filling
    # and must not delay it); thereafter each round batches both
    # sessions' due refits into one program, with session 1's final
    # refit left pending for the explicit drain.
    assert len(deltas) == 5
    deltas += eng.flush_streams()
    assert len(deltas) == 6
    assert deltas[0][1].refit_index == 0 and deltas[-1][1].refit_index == 2

    # Engine's batched refit == the standalone rolling path on the same
    # rows (vmap-vs-single tolerance).
    roll = window.RollingVarLiNGAM(d, chunk, wc, lags=1, config=cfg.fit)
    for rows in all_chunks[0]:
        roll.push(rows)
    solo = roll.refit()
    served = eng.stream_session(sids[0]).last_fit
    assert np.array_equal(
        np.asarray(solo.result.order), np.asarray(served.result.order)
    )
    np.testing.assert_allclose(
        np.asarray(solo.result.adjacency),
        np.asarray(served.result.adjacency),
        atol=1e-5,
    )
    closed = eng.close_stream(sids[0])
    assert closed.n_refits == 3
    assert sids[0] not in eng._streams


def test_engine_idle_filling_session_does_not_starve_active():
    """A session still filling its window must not block auto-flush for
    sessions that are due (regression: liveness under stalled clients)."""
    d, chunk, wc = 6, 64, 3
    cfg = _stream_config(d, chunk, wc)
    eng = CausalDiscoveryEngine(batch_size=8)
    active = eng.open_stream(cfg)
    eng.open_stream(cfg)  # never posts; window never fills
    chunks = _stock_chunks(d, chunk, wc + 2, seed=11)
    deltas = []
    for rows in chunks:
        deltas += eng.post_chunk(active, rows)
    assert len(deltas) == 3
    assert all(sid == active for sid, _ in deltas)


def test_engine_ready_idle_session_defers_at_most_one_post():
    """A ready-but-idle peer may defer an active session's due refit by
    one of its own posts, never indefinitely (bounded-deferral rule)."""
    d, chunk, wc = 6, 64, 3
    cfg = _stream_config(d, chunk, wc)
    eng = CausalDiscoveryEngine(batch_size=8)
    active, idle = eng.open_stream(cfg), eng.open_stream(cfg)
    chunks = _stock_chunks(d, chunk, wc + 4, seed=13)
    for rows in chunks[:wc]:  # both windows fill; idle stops posting
        eng.post_chunk(idle, rows)
        eng.post_chunk(active, rows)
    eng.flush_streams()
    n_refits_before = eng.stream_session(active).n_refits
    deltas = []
    for rows in chunks[wc:]:  # 4 posts from the active session only
        deltas += eng.post_chunk(active, rows)
    assert all(sid == active for sid, _ in deltas)
    # Due after post 1, flushed at post 2; due at 3, flushed at 4.
    assert len(deltas) == 2
    assert eng.stream_session(active).n_refits == n_refits_before + 2


def test_engine_refit_every_throttles():
    d, chunk, wc = 6, 64, 3
    cfg = _stream_config(d, chunk, wc, refit_every=2)
    eng = CausalDiscoveryEngine(batch_size=1)
    sid = eng.open_stream(cfg)
    chunks = _stock_chunks(d, chunk, wc + 4, seed=7)
    n_deltas = sum(len(eng.post_chunk(sid, rows)) for rows in chunks)
    # Ready after wc pushes; 4 more pushes at refit_every=2 -> 2 refits.
    assert n_deltas == 2
    assert eng.stream_session(sid).n_refits == 2


def test_engine_flush_compiles_once_per_shape_bucket():
    """A steady flush cadence reuses the batched refit program: after
    the warmup rounds have traced each (bucket, shape) signature —
    visible in the public ``repro.obs.compile_log`` — further full
    rounds add zero compile events."""
    d, chunk, wc = 5, 48, 3  # unique dims so other tests' caches can't mask
    cfg = _stream_config(d, chunk, wc)
    eng = CausalDiscoveryEngine(batch_size=4)
    all_chunks = [_stock_chunks(d, chunk, wc + 6, seed=s) for s in (21, 22)]
    sids = [eng.open_stream(cfg) for _ in all_chunks]
    n0 = compile_log.total("batched.fit_many_from_stats")
    for k in range(wc + 2):
        for sid, chunks in zip(sids, all_chunks):
            eng.post_chunk(sid, chunks[k])
    eng.flush_streams()
    n_warm = compile_log.total("batched.fit_many_from_stats")
    assert n_warm > n0  # the fill/steady shape signatures traced once...
    for k in range(wc + 2, wc + 4):
        for sid, chunks in zip(sids, all_chunks):
            eng.post_chunk(sid, chunks[k])
    eng.flush_streams()
    # ...and two more full rounds replay them without re-tracing.
    assert compile_log.total("batched.fit_many_from_stats") == n_warm
    assert not eng.last_flush_errors


def test_engine_flush_isolates_failing_session(monkeypatch):
    """One session failing to build its refit plan must not abort the
    flush: peers refit, the failure lands in ``last_flush_errors`` as a
    structured event, and the broken session stays due (retryable)."""
    d, chunk, wc = 6, 64, 3
    cfg = _stream_config(d, chunk, wc)
    eng = CausalDiscoveryEngine(batch_size=8)
    good, bad = eng.open_stream(cfg), eng.open_stream(cfg)
    chunks = _stock_chunks(d, chunk, wc, seed=31)
    for rows in chunks:  # fill both windows; both become due
        eng.stream_session(good).post(rows)
        eng.stream_session(bad).post(rows)

    def boom():
        raise RuntimeError("poisoned moment state")

    monkeypatch.setattr(
        eng.stream_session(bad).rolling, "prepare_refit", boom
    )
    out = eng.flush_streams()
    assert [sid for sid, _ in out] == [good]
    (err,) = eng.last_flush_errors
    assert (err.sid, err.stage) == (bad, "prepare")
    assert isinstance(err.error, RuntimeError)
    assert "poisoned" in err.summary()
    assert eng.stream_session(bad).due  # still due: next flush retries


def test_engine_flush_falls_back_per_session_on_bucket_failure(monkeypatch):
    """A whole-bucket program failure degrades to per-session refits —
    every session still gets its delta, and the bucket-level error is
    recorded with sid='*'."""
    d, chunk, wc = 6, 64, 3
    cfg = _stream_config(d, chunk, wc)
    eng = CausalDiscoveryEngine(batch_size=8)
    sids = [eng.open_stream(cfg) for _ in range(2)]
    for k, rows in enumerate(_stock_chunks(d, chunk, wc, seed=33)):
        for sid in sids:
            eng.stream_session(sid).post(rows)

    def boom(*a, **kw):
        raise RuntimeError("bucket program OOM")

    monkeypatch.setattr(
        serve_engine.lingam_batched, "fit_many_from_stats", boom
    )
    out = eng.flush_streams()
    assert sorted(sid for sid, _ in out) == sorted(sids)
    (err,) = eng.last_flush_errors
    assert (err.sid, err.stage) == ("*", "fit")
    assert all(not eng.stream_session(sid).due for sid in sids)