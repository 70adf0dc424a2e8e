"""Performance-accounting layer: cost capture, roofline math, pins.

Covers the profiling PR's contracts:

  * disabled profiling is a plain passthrough: `FitResult`s are
    bit-identical to the enabled run, warm programs never recompile,
    and no cost records appear — the same zero-delta pin spans carry.
  * cost records are keyed with the exact `compile_log` scheme, so the
    captured signatures across fit / bootstrap / query paths are a
    subset of the compile-event keys (the join contract).
  * captured records carry XLA `cost_analysis` FLOPs/bytes and
    `memory_analysis` watermarks and accumulate call statistics.
  * the analytic pairwise-moments cost model matches the hand-computed
    FLOP/byte oracle, and `utilization`/`roofline_terms` reproduce the
    roofline arithmetic exactly.
  * the device-peaks registry resolves by device-kind substring and
    honors the `REPRO_PEAKS` calibration override.
  * the HLO collective-bytes parser and the stage-attribution report
    machinery (`analysis.report`) keep their schemas.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import api, batched
from repro.infer import query as query_lib
from repro.obs import compile_log, profile

_CFG = api.FitConfig(backend="blocked", compaction="staged")


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.disable()
    profile.disable()
    obs.reset_all()
    yield
    obs.disable()
    profile.disable()
    obs.reset_all()


def _data(m=192, d=6, seed=0):
    rng = np.random.default_rng(seed)
    w = np.triu(rng.uniform(0.3, 0.8, (d, d)), 1) * (rng.random((d, d)) < 0.5)
    e = rng.laplace(size=(m, d)).astype(np.float32)
    return np.linalg.solve(np.eye(d) - w.T, e.T).T.astype(np.float32)


def _leaves(res):
    return [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(res)]


# ---------------------------------------------------------------------------
# disabled-path pin: bit-identical results, zero compile delta, no records
# ---------------------------------------------------------------------------


def test_disabled_profiling_is_bit_identical_and_recordless():
    x = jnp.asarray(_data())
    base = api.fit_fn(x, _CFG)

    profile.enable()
    on = api.fit_fn(x, _CFG)
    assert profile.records(), "enabled profiling captured nothing"

    profile.disable()
    profile.reset()
    off = api.fit_fn(x, _CFG)
    assert profile.records() == [], "disabled profiling left records"

    for a, b, c in zip(_leaves(base), _leaves(on), _leaves(off)):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(a, c)


def test_disabled_profiling_adds_no_compiles_on_warm_programs():
    x = jnp.asarray(_data())
    api.fit_fn(x, _CFG)  # warm the program
    compile_log.reset()

    for _ in range(3):
        api.fit_fn(x, _CFG)  # warm + disabled: no retrace, no capture
    assert compile_log.total() == 0
    assert profile.records() == []


def test_call_passthrough_forwards_args_and_result():
    profile.disable()
    out = profile.call(lambda a, b=0: a + b, 2, b=3, op="noop")
    assert out == 5
    assert profile.get("noop") is None


# ---------------------------------------------------------------------------
# key-join contract: profile keys are a subset of compile_log keys
# ---------------------------------------------------------------------------


def _compile_keys():
    return {(e["op"], tuple(e["shape"]), e["config"])
            for e in compile_log.events()}


def _profile_keys():
    return {(r.op, tuple(r.shape), r.config) for r in profile.records()}


def test_cost_keys_join_compile_log_across_fit_bootstrap_query():
    profile.enable()
    x = _data(m=160, d=5)
    xj = jnp.asarray(x)

    res = api.fit_fn(xj, _CFG)

    rng = np.random.default_rng(0)
    idx = jnp.asarray(
        rng.integers(0, x.shape[0], size=(3, x.shape[0])), dtype=jnp.int32
    )
    batched.bootstrap_fits(xj, idx, _CFG)

    eng = query_lib.QueryEngine()
    eng.run([query_lib.EffectQuery(graph=res),
             query_lib.EffectQuery(graph=res)])

    prof = _profile_keys()
    assert prof, "no cost records captured"
    ops = {k[0] for k in prof}
    assert "core.fit" in ops
    assert "batched.bootstrap_fits" in ops
    assert "query.effects" in ops
    missing = prof - _compile_keys()
    assert not missing, f"cost keys with no compile event: {missing}"
    assert np.asarray(res.order).shape == (5,)


def test_capture_records_cost_and_memory_watermarks():
    profile.enable()
    x = jnp.asarray(_data(m=256, d=8))
    api.fit_fn(x, _CFG)
    api.fit_fn(x, _CFG)

    rec = profile.get("core.fit", x.shape, _CFG)
    assert rec is not None
    assert rec.source == "measured"
    assert rec.flops > 0 and rec.bytes_accessed > 0
    assert rec.arg_bytes >= x.size * 4  # at least the input slab
    assert rec.calls == 2
    assert 0 < rec.best_s <= rec.total_s

    row = rec.row(profile.DevicePeaks("t", 1e12, 1e11, 1e10))
    assert row["op"] == "core.fit" and row["calls"] == 2
    assert row["gflops_per_s"] > 0 and row["bound"] in ("compute", "memory")
    json.dumps(row)  # JSON-safe

    snap = profile.snapshot()
    assert snap["device"]["name"]
    assert any(r["op"] == "core.fit" for r in snap["records"])


# ---------------------------------------------------------------------------
# roofline math vs the hand-computed pairwise_moments oracle
# ---------------------------------------------------------------------------


def test_analytic_cost_matches_hand_oracle():
    m, d = 256, 8
    # 35 flops per (pair, sample): residual, log cosh, u*exp(-u^2/2),
    # two accumulates — times d*d pairs times m samples.
    want_flops = 35 * d * d * m
    # fp32 streamed traffic: x and its standardized copy read (2*m*d),
    # both (d, d) moment outputs written.
    want_bytes = 4 * (2 * m * d + 2 * d * d)

    got = profile.analytic_cost("pairwise_moments", (m, d))
    assert got["flops"] == pytest.approx(want_flops)
    assert got["bytes"] == pytest.approx(want_bytes)
    assert got["intensity"] == pytest.approx(want_flops / want_bytes)

    tile = 4
    got_rows = profile.analytic_cost("pairwise_moment_sums_rows",
                                     (tile, d, m))
    assert got_rows["flops"] == pytest.approx(35 * tile * d * m)
    assert got_rows["bytes"] == pytest.approx(
        4 * (m * tile + m * d + 2 * tile * d))

    assert profile.analytic_cost("unknown_op", (m, d)) is None
    assert profile.analytic_cost("pairwise_moments", None) is None


def test_utilization_reproduces_roofline_arithmetic():
    peaks = profile.DevicePeaks("toy", flops_per_s=100e9, hbm_bw=20e9,
                                ici_bw=10e9)
    flops, nbytes, secs = 35 * 8 * 8 * 256, 4 * (2 * 256 * 8 + 2 * 64), 1e-3
    u = profile.utilization(flops, nbytes, secs, peaks)

    assert u["gflops_per_s"] == pytest.approx(flops / secs / 1e9)
    assert u["gbytes_per_s"] == pytest.approx(nbytes / secs / 1e9)
    t_compute, t_memory = flops / 100e9, nbytes / 20e9
    assert u["roofline_frac"] == pytest.approx(
        max(t_compute, t_memory) / secs)
    assert u["bound"] == ("compute" if t_compute >= t_memory else "memory")
    assert u["peaks"] == "toy"

    # compute-bound corner: huge flops, tiny traffic
    u2 = profile.utilization(1e12, 1.0, 1.0, peaks)
    assert u2["bound"] == "compute"
    assert u2["roofline_frac"] == pytest.approx(10.0)  # 1e12/100e9 per 1s


def test_roofline_terms_wrapper_agrees():
    from repro.analysis import roofline

    peaks = profile.DevicePeaks("toy", 100e9, 20e9, 10e9)
    t = roofline.roofline_terms(1e9, 1e9, 5e8, peaks=peaks)
    assert t["compute_s"] == pytest.approx(1e9 / 100e9)
    assert t["memory_s"] == pytest.approx(1e9 / 20e9)
    assert t["collective_s"] == pytest.approx(5e8 / 10e9)
    assert t["dominant"] == "memory"
    assert t["bound_s"] == pytest.approx(max(1e9 / 100e9, 1e9 / 20e9))


# ---------------------------------------------------------------------------
# device-peaks registry
# ---------------------------------------------------------------------------


def test_device_peaks_resolution_and_override(monkeypatch):
    monkeypatch.delenv("REPRO_PEAKS", raising=False)
    assert profile.device_peaks("NVIDIA H100 80GB HBM3").name == "gpu-h100"
    assert profile.device_peaks("TPU v4").name == "tpu-v4"
    assert profile.device_peaks("cpu").name == "cpu-generic"
    assert profile.device_peaks("TPU v5 lite").name == "tpu-v5e"
    with pytest.raises(KeyError, match="weird accelerator"):
        profile.device_peaks("weird accelerator")
    # the process's own device resolves to *something* in the table
    assert profile.device_peaks().flops_per_s > 0

    monkeypatch.setenv("REPRO_PEAKS", "flops=3.2e12,hbm=80e9,name=calibrated")
    p = profile.device_peaks("cpu")
    assert p.name == "calibrated"
    assert p.flops_per_s == pytest.approx(3.2e12)
    assert p.hbm_bw == pytest.approx(80e9)
    assert p.ici_bw == pytest.approx(10e9)  # untouched field survives


# ---------------------------------------------------------------------------
# HLO collective-bytes parser (the surviving piece of the LM scaffold)
# ---------------------------------------------------------------------------


def test_collective_bytes_parses_optimized_hlo():
    hlo = """
HloModule m
ENTRY main {
  %p0 = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(%p0), replica_groups={}
  %ag = f32[256,256]{1,0} all-gather(%ar), dimensions={0}
  ROOT %t = (f32[256,256]) tuple(%ag)
}
"""
    got = profile.collective_bytes(hlo)
    assert got["all-reduce"] == 128 * 256 * 4
    assert got["all-gather"] == 128 * 256 * 4  # operand bytes, not result
    assert got["reduce-scatter"] == 0


# ---------------------------------------------------------------------------
# stage-attribution report
# ---------------------------------------------------------------------------


def test_live_attribution_rows_carry_schema():
    from repro.analysis import report

    payload = report.live_attribution(m=128, d=5, backend="blocked",
                                      repeats=1, include_pallas=False)
    stages = {r["stage"] for r in payload["rows"]}
    assert {"ordering", "pruning", "solve", "full_fit"} <= stages
    for row in payload["rows"]:
        for key in report.STAGE_KEYS:
            assert key in row, f"stage row missing {key}"
        assert row["best_s"] > 0
    assert payload["kernels"], "no kernel-variant rows"
    assert payload["kernels"][0]["backend"] == "blocked"
    text = report.render(payload)
    assert "per-stage attribution" in text and "full_fit" in text


def test_report_smoke_validates_committed_artifact():
    from repro.analysis import report

    assert report.smoke() == 0, "committed BENCH_profile.json failed smoke"
