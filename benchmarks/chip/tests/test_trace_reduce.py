import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = os.path.join(DATA, "small_fit.xplane.pb")


def test_union_merges_overlaps_and_nesting():
    merged = trace_reduce.union([(5, 9), (0, 2), (1, 3), (6, 7), (10, 12)])
    assert merged == [[0, 3], [5, 9], [10, 12]]


def test_self_times_subtract_nested_ops():
    events = [(0, 100, "while"), (10, 40, "pallas:k"), (50, 90, "pallas:k"),
              (60, 70, "fusion"), (100, 130, "fusion")]
    got = trace_reduce.self_times(events)
    assert got["while"] == pytest.approx(30e-9)
    assert got["pallas:k"] == pytest.approx(60e-9)
    assert got["fusion"] == pytest.approx(40e-9)


def test_op_kinds():
    kernel = ('%pairwise_moments_pallas.72 = (f32[8,8]) custom-call(f32[8,8] '
              '%pad.501), custom_call_target="tpu_custom_call"')
    lu = ('%custom-call.473 = (f32[18,964,128]) custom-call(%slice.2720), '
          'custom_call_target="LuDecompositionBlock"')
    assert trace_reduce.op_kind(kernel) == "pallas:pairwise_moments_pallas"
    assert trace_reduce.op_kind(lu) == "custom-call:LuDecompositionBlock"
    assert trace_reduce.op_kind("%fusion.12 = f32[4] fusion(%p)") == "fusion"
    assert trace_reduce.module_name("jit__fit_local(82712637862710922)") == \
        "jit__fit_local"


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.fail(f"recorded trace missing: {RECORDED}")
    return trace_reduce.reduce_file(RECORDED)


def test_recorded_trace_window_and_busy_time(recorded):
    """A staged fit traced on one TPU v5e (``record_trace.py``)."""
    assert 0.0 < recorded["busy_s"] <= recorded["window_s"]
    assert recorded["window_s"] < 60.0


def test_recorded_trace_keeps_every_kernel_of_the_fit(recorded):
    """The device clock lags the host's; no kernel may fall outside the
    window for it. The fit ran 64 ordering steps, one kernel each."""
    import jax

    pd = jax.profiler.ProfileData.from_file(RECORDED)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    kernels = [e.duration_ns for e in ops.events
               if trace_reduce.KERNEL_MARK in e.name]
    assert len(kernels) == 64
    assert recorded["kernel_s"] == pytest.approx(sum(kernels) * 1e-9)


def test_recorded_trace_kernel_inside_the_fit_program(recorded):
    fit = recorded["modules"]["jit__fit_local"]
    inside = recorded["module_kernel_s"]["jit__fit_local"]
    assert 0.0 < recorded["kernel_s"] == pytest.approx(inside)
    assert inside < fit <= recorded["window_s"]
    assert recorded["busy_s"] == pytest.approx(fit, rel=1e-3)
    kinds = dict(recorded["device_ops"])
    assert kinds["pallas:pairwise_moments_pallas"] == pytest.approx(
        recorded["kernel_s"])


def test_recorded_trace_self_times_sum_to_busy_time(recorded):
    total = sum(s for _, s in recorded["device_ops"])
    assert total == pytest.approx(recorded["busy_s"], rel=1e-6)


def test_recorded_trace_idle_time_is_charged_to_host_events(recorded):
    idle = sum(s for _, s in recorded["idle_gaps"])
    assert idle == pytest.approx(
        recorded["window_s"] - recorded["busy_s"], rel=1e-6, abs=1e-9)
    assert recorded["idle_gaps"], "a traced fit has host time between ops"


def _reader(name):
    import run

    return run.load_module(os.path.join(run.HERE, "metrics", name + ".py"),
                           "metric_" + name.replace(".", "_"))


def _renamed(reduced, old, new):
    out = dict(reduced)
    for key in ("modules", "module_kernel_s"):
        out[key] = {new if k == old else k: v for k, v in reduced[key].items()}
    return out


def test_program_readers_find_the_fit_program_by_its_kernel(recorded):
    """``fit_other_ms`` and ``var_regress_ms`` read the fit program as the
    one that runs the kernel: a renamed program reads the same."""
    reduced = dict(recorded, graphs=1)
    fit = recorded["modules"]["jit__fit_local"]
    other = _reader("fit_other_ms.fit").read(reduced, {})
    assert other == pytest.approx(
        1e3 * (fit - recorded["module_kernel_s"]["jit__fit_local"]))
    outside = _reader("var_regress_ms").read(reduced, {})
    assert outside == pytest.approx(
        1e3 * (sum(recorded["modules"].values()) - fit))
    renamed = _renamed(reduced, "jit__fit_local", "jit_fit_program")
    assert _reader("fit_other_ms.fit").read(renamed, {}) == other
    assert _reader("var_regress_ms").read(renamed, {}) == outside


@pytest.mark.parametrize("name", ["fit_other_ms.fit", "var_regress_ms",
                                  "moment_kernel_ms.fit",
                                  "moment_gpairs_per_s.fit"])
def test_readers_are_silent_where_no_kernel_ran(recorded, name):
    """A kernel taken off the path leaves its metrics out, never 0."""
    reduced = dict(recorded, graphs=1, kernel_s=0.0,
                   module_kernel_s={k: 0.0 for k in
                                    recorded["module_kernel_s"]})
    assert _reader(name).read(reduced, {"pair_samples": 1}) is None
