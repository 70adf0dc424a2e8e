"""Kernel autotuning & dispatch subsystem: registry constraints, cache
round trip, offline determinism, and the tuned-vs-heuristic bit-parity
contract."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.tune import autotune, cache, registry
from repro.obs import compile_log, metrics as obs_metrics, trace as obs_trace

RNG = np.random.default_rng(7)


def _make(m, d):
    x = RNG.laplace(size=(m, d)).astype(np.float32)
    xs = ops.standardize(jnp.asarray(x))
    return jnp.asarray(x), xs, ops.correlation(xs)


def _tmp_table(tmp_path):
    return cache.TuneTable(
        overlay_path_=str(tmp_path / "overlay.json")
    )


# ---------------------------------------------------------------------------
# Registry / dispatch
# ---------------------------------------------------------------------------


def test_heuristic_matches_legacy_pick_blocks():
    """The heuristic keeps the old ops._pick_blocks rows and sample
    blocks; below 128 columns bj is the whole padded column extent,
    the only sub-lane block the TPU accepts."""
    legacy = {
        (300, 4): (8, 8, 256),
        (300, 16): (8, 16, 256),
        (600, 64): (8, 64, 512),
        (600, 128): (8, 128, 512),
        (5000, 200): (8, 128, 2048),
    }
    for (m, d), want in legacy.items():
        assert registry.heuristic_pair_blocks(d, m) == want, (m, d)
        plan = registry.dispatch(
            "pairwise_moments", (m, d), backend="pallas", mode="off"
        )
        assert (plan.bi, plan.bj, plan.bm) == want


def test_dispatch_unknown_op_and_mode():
    with pytest.raises(ValueError, match="no kernel variant"):
        registry.dispatch("nope", (64, 8))
    with pytest.raises(ValueError, match="unknown tune mode"):
        registry.dispatch("pairwise_moments", (64, 8), mode="bogus")


def test_dispatch_mesh_compatibility():
    """The pair-tile kernel is local-only; the row-tile variant is the
    shard_map-safe one."""
    with pytest.raises(ValueError, match="not mesh-compatible"):
        registry.dispatch(
            "pairwise_moments", (64, 8), backend="pallas", mesh=True
        )
    plan = registry.dispatch(
        "pairwise_moment_sums_rows", (8, 8, 64), backend="pallas", mesh=True
    )
    assert plan.variant == "pallas-row-tile"


def test_candidates_respect_constraints():
    for op, shape, chunk in [
        ("pairwise_moments", (4096, 256), None),
        ("pairwise_moment_sums_rows", (64, 128, 2048), 512),
    ]:
        var = registry.get_variant(op, "pallas")
        cands = autotune.candidate_plans(
            op, shape, backend="pallas", chunk=chunk
        )
        assert len(cands) > 1
        for p in cands[1:]:  # [0] is the heuristic, kept unconditionally
            assert p.bi % 8 == 0 and p.bj % 8 == 0
            assert p.bm % registry.ACCUM_CHUNK == 0
            assert registry.vmem_bytes(p.bi, p.bj, p.bm) <= (
                var.constraints.vmem_budget
            )
            if chunk:
                assert p.bm <= chunk


def test_default_interpret_tracks_backend():
    """interpret=None resolves from the detected backend: the Pallas
    interpreter only when no accelerator backs the process."""
    expect = jax.default_backend() == "cpu"
    assert registry.default_interpret() is expect
    assert registry.resolve_interpret(None) is expect
    assert registry.resolve_interpret(True) is True
    assert registry.resolve_interpret(False) is False


# ---------------------------------------------------------------------------
# Cache: round trip + offline mode
# ---------------------------------------------------------------------------


def test_cache_round_trip_identical_dispatch(tmp_path):
    """Autotune (tiny grid, interpret mode on CPU) -> overlay write ->
    fresh reload -> dispatch returns the identical plan."""
    table = _tmp_table(tmp_path)
    tuned = autotune.autotune_op(
        "pairwise_moments", (128, 8), backend="pallas",
        interpret=True, quick=True, repeats=1, table=table,
    )
    # reload from disk into a brand-new table
    table2 = cache.TuneTable(overlay_path_=table.overlay_path)
    plan = registry.dispatch(
        "pairwise_moments", (128, 8), backend="pallas", table=table2
    )
    assert plan == tuned.best
    assert plan.source == "tuned"
    # the persisted entry is versioned + bucketed
    payload = json.load(open(table.overlay_path))
    assert payload["version"] == cache.SCHEMA_VERSION
    (key,) = payload["entries"].keys()
    assert key == tuned.key
    assert key.startswith(f"v{cache.SCHEMA_VERSION}/")


def test_plan_keys_separate_backends(tmp_path):
    """Blocked and pallas tunings at the same (op, dtype, bucket) must
    not collide: both stay retrievable."""
    table = _tmp_table(tmp_path)
    tb = autotune.autotune_op(
        "pairwise_moments", (128, 8), backend="blocked",
        quick=True, repeats=1, table=table,
    )
    tp = autotune.autotune_op(
        "pairwise_moments", (128, 8), backend="pallas",
        interpret=True, quick=True, repeats=1, table=table,
    )
    assert tb.key != tp.key
    got_b = registry.dispatch(
        "pairwise_moments", (128, 8), backend="blocked", table=table
    )
    got_p = registry.dispatch(
        "pairwise_moments", (128, 8), backend="pallas", table=table
    )
    assert got_b == tb.best and got_b.backend == "blocked"
    assert got_p == tp.best and got_p.backend == "pallas"


def test_auto_mode_never_searches_inside_a_trace(tmp_path, monkeypatch):
    """tune="auto" inside a jit trace degrades to the heuristic (the
    timed search would absorb tracing overhead and persist distorted
    plans); the search belongs to eager dispatch points (warm-up)."""
    import jax

    from repro.core import api
    from repro.data.simulate import simulate_lingam

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "auto.json"))
    cache.reset_table()
    try:
        gt = simulate_lingam(m=70, d=5, seed=9)
        x = jnp.asarray(gt.data)
        # distinct shape bucket from every other test so the jit cache
        # cannot have a stale entry for this (shape, config) pair
        ref = api.fit_fn(x, api.FitConfig(backend="blocked", tune="off"))
        got = api.fit_fn(x, api.FitConfig(backend="blocked", tune="auto"))
        assert np.array_equal(np.asarray(ref.order), np.asarray(got.order))
        assert not os.path.exists(str(tmp_path / "auto.json"))
        assert jax.core.trace_ctx.is_top_level()
    finally:
        cache.reset_table()


def test_recorded_invalid_plan_degrades_without_research(tmp_path):
    """An entry that fails validation for the dispatch shape falls back
    to the heuristic deterministically — auto mode must not re-run the
    search once any entry exists for the bucket."""
    table = _tmp_table(tmp_path)
    key = cache.plan_key(
        registry.device_kind(), "pairwise_moments", "pallas", "float32",
        cache.shape_bucket("pairwise_moments", (300, 20)),
    )
    # bm not a multiple of ACCUM_CHUNK -> validate() rejects it
    table.record(key, {
        "variant": "pallas-pair-tile", "backend": "pallas",
        "bi": 8, "bj": 8, "bm": 96, "block": 0,
    })
    calls = []
    orig = autotune.autotune_op

    def spy(*a, **k):
        calls.append(a)
        return orig(*a, **k)

    autotune.autotune_op = spy
    try:
        p1 = registry.dispatch(
            "pairwise_moments", (300, 20), backend="pallas", mode="auto",
            table=table,
        )
        p2 = registry.dispatch(
            "pairwise_moments", (300, 20), backend="pallas", mode="auto",
            table=table,
        )
    finally:
        autotune.autotune_op = orig
    assert not calls  # entry exists -> no search, even though invalid
    heur = registry.dispatch(
        "pairwise_moments", (300, 20), backend="pallas", mode="off"
    )
    assert p1 == p2 == heur


def test_shape_bucketing_shares_plans(tmp_path):
    """Shapes in the same power-of-two bucket hit the same entry."""
    table = _tmp_table(tmp_path)
    autotune.autotune_op(
        "pairwise_moments", (100, 7), backend="blocked",
        quick=True, repeats=1, table=table,
    )
    a = registry.dispatch(
        "pairwise_moments", (100, 7), backend="blocked", table=table
    )
    b = registry.dispatch(
        "pairwise_moments", (97, 5), backend="blocked", table=table
    )
    assert a == b and a.source == "tuned"


def test_offline_mode_is_heuristic_and_deterministic(tmp_path):
    table = _tmp_table(tmp_path)
    autotune.autotune_op(
        "pairwise_moments", (128, 8), backend="pallas",
        interpret=True, quick=True, repeats=1, table=table,
    )
    offline = cache.TuneTable(
        overlay_path_=table.overlay_path, offline=True
    )
    assert offline.lookup(cache.plan_key(
        registry.device_kind(), "pairwise_moments", "pallas", "float32",
        cache.shape_bucket("pairwise_moments", (128, 8)),
    )) is None
    p1 = registry.dispatch(
        "pairwise_moments", (128, 8), backend="pallas", table=offline
    )
    p2 = registry.dispatch(
        "pairwise_moments", (128, 8), backend="pallas", mode="off",
        table=table,
    )
    assert p1 == p2 and p1.source == "heuristic"
    with pytest.raises(RuntimeError, match="offline"):
        offline.record("k", {})


def test_env_overlay_path(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "env.json"))
    assert cache.overlay_path() == str(tmp_path / "env.json")
    # Unset, no overlay is read or written: only committed plans count.
    monkeypatch.delenv("REPRO_TUNE_CACHE")
    assert cache.overlay_path() is None
    table = cache.TuneTable()
    table.record("k", {"bi": 8})
    assert table.lookup("k") == {"bi": 8} and table.overlay_path is None


# ---------------------------------------------------------------------------
# Parity: tuned plans == heuristic plans, bit for bit
# ---------------------------------------------------------------------------


def test_pair_op_parity_bit_identical_across_plans():
    """Every candidate block shape (the grid the tuner searches) returns
    bit-identical moments: bi/bj only re-tile the pair space, and bm is
    accumulated in fixed ACCUM_CHUNK sub-sums."""
    _, xs, c = _make(700, 24)
    heur = registry.dispatch(
        "pairwise_moments", (700, 24), backend="pallas", mode="off"
    )
    ref1, ref2 = ops.pairwise_moments(
        xs, c, backend="pallas", interpret=True, plan=heur
    )
    cands = autotune.candidate_plans(
        "pairwise_moments", (700, 24), backend="pallas"
    )
    assert len(cands) > 3
    for p in cands:
        m1, m2 = ops.pairwise_moments(
            xs, c, backend="pallas", interpret=True, plan=p
        )
        assert np.array_equal(np.asarray(ref1), np.asarray(m1)), p
        assert np.array_equal(np.asarray(ref2), np.asarray(m2)), p


def test_blocked_parity_bit_identical_across_blocks():
    _, xs, c = _make(700, 24)
    heur = registry.dispatch(
        "pairwise_moments", (700, 24), backend="blocked", mode="off"
    )
    ref1, ref2 = ops.pairwise_moments(xs, c, backend="blocked", plan=heur)
    for p in autotune.candidate_plans(
        "pairwise_moments", (700, 24), backend="blocked"
    ):
        m1, m2 = ops.pairwise_moments(xs, c, backend="blocked", plan=p)
        assert np.array_equal(np.asarray(ref1), np.asarray(m1)), p
        assert np.array_equal(np.asarray(ref2), np.asarray(m2)), p


def test_rows_op_parity_bit_identical_across_plans():
    _, xs, c = _make(512, 16)
    heur = registry.dispatch(
        "pairwise_moment_sums_rows", (16, 16, 512), backend="pallas",
        mode="off", chunk=512,
    )
    r1, r2 = ops.pairwise_moment_sums_rows(
        xs, c, 0, 16, chunk=512, backend="pallas", interpret=True,
        plan=heur,
    )
    for p in autotune.candidate_plans(
        "pairwise_moment_sums_rows", (16, 16, 512), backend="pallas",
        chunk=512,
    ):
        s1, s2 = ops.pairwise_moment_sums_rows(
            xs, c, 0, 16, chunk=512, backend="pallas", interpret=True,
            plan=p,
        )
        assert np.array_equal(np.asarray(r1), np.asarray(s1)), p
        assert np.array_equal(np.asarray(r2), np.asarray(s2)), p


def test_fit_results_identical_with_tuned_table(tmp_path):
    """End-to-end: a fit dispatched through a tuned table returns the
    same FitResult leaves as the offline heuristic fit."""
    from repro.core import api
    from repro.data.simulate import simulate_lingam

    table = _tmp_table(tmp_path)
    autotune.autotune_op(
        "pairwise_moments", (250, 9), backend="blocked",
        quick=True, repeats=1, table=table,
    )
    gt = simulate_lingam(m=250, d=9, seed=3)
    x = jnp.asarray(gt.data)
    ref = api.fit_fn(x, api.FitConfig(backend="blocked", tune="off"))
    # route the singleton table through the process cache
    os.environ["REPRO_TUNE_CACHE"] = table.overlay_path
    cache.reset_table()
    try:
        got = api.fit_fn(x, api.FitConfig(backend="blocked", tune="cache"))
    finally:
        del os.environ["REPRO_TUNE_CACHE"]
        cache.reset_table()
    assert np.array_equal(np.asarray(ref.order), np.asarray(got.order))
    assert np.array_equal(
        np.asarray(ref.adjacency), np.asarray(got.adjacency)
    )
    assert np.array_equal(
        np.asarray(ref.resid_var), np.asarray(got.resid_var)
    )


# ---------------------------------------------------------------------------
# Config plumbing + engine warm-up
# ---------------------------------------------------------------------------


def test_fitconfig_tune_validation():
    from repro.core import api

    api.FitConfig(tune="off")
    api.FitConfig(tune="auto")
    with pytest.raises(ValueError, match="tune"):
        api.FitConfig(tune="always")


def test_engine_warmup_resolves_plans_and_compiles(tmp_path, monkeypatch):
    from repro.core import api
    from repro.serve.engine import CausalDiscoveryEngine, FitRequest

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "warm.json"))
    cache.reset_table()
    try:
        eng = CausalDiscoveryEngine(
            api.FitConfig(backend="blocked", compaction="staged",
                          min_stage=3, tune="cache")
        )
        n0 = compile_log.total("batched.fit_many")
        plans = eng.warmup([(64, 5)])
        assert plans and all(
            isinstance(p, registry.Plan) for p in plans.values()
        )
        # Warmup pre-compiled the vmap fit program (public compile-log
        # pin: one event per (shape, config) signature).
        n_warm = compile_log.total("batched.fit_many")
        assert n_warm == n0 + 1
        x = RNG.laplace(size=(64, 5)).astype(np.float32)
        (req,) = eng.run([FitRequest(data=x)])
        assert sorted(req.result.order.tolist()) == list(range(5))
        # Steady state: the warmed shape serves with zero new compiles.
        assert compile_log.total("batched.fit_many") == n_warm
    finally:
        cache.reset_table()


def test_dispatch_telemetry_counts_variants(tmp_path, monkeypatch):
    """Enabled telemetry counts each dispatch by (op, variant, source)
    and never changes the resolved plan."""
    from repro import obs

    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "t.json"))
    cache.reset_table()
    try:
        plain = registry.dispatch(
            "pairwise_moments", (512, 16), backend="blocked", mode="off"
        )
        obs.enable()
        obs_metrics.reset()
        try:
            traced = registry.dispatch(
                "pairwise_moments", (512, 16), backend="blocked",
                mode="off",
            )
            snap = obs_metrics.snapshot()["counters"]
        finally:
            obs.disable()
            obs_metrics.reset()
            obs_trace.reset()
        assert traced == plain
        (key,) = [k for k in snap if k.startswith("kernels.dispatch")]
        assert f'variant="{plain.variant}"' in key
        assert 'source="heuristic"' in key
        assert snap[key] == 1.0
    finally:
        cache.reset_table()


def test_rolling_window_moment_chunk_defaults_to_stream_chunk():
    """With an empty table the dispatcher-chosen moment_chunk degrades
    to the stream chunk exactly (the legacy default)."""
    from repro.stream.window import RollingVarLiNGAM

    r = RollingVarLiNGAM(d=4, chunk=64, window_chunks=3)
    assert r.config.moment_chunk == 64
