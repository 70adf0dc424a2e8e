"""The per-stage reduction of a trace (``xplane_scopes.py``): on a message
built by hand, on the scoped trace (``record_scoped_trace.py``: a staged
DirectLiNGAM and a staged VarLiNGAM fit on one TPU v5e) and on the
committed trace of a program without stage names."""

import os

import pytest

import trace_reduce
import xplane_scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
UNSCOPED_TRACE = os.path.join(DATA, "small_fit.xplane.pb")
SCOPED_TRACE = os.path.join(DATA, "small_fit_scoped.xplane.pb")
STAGES = ("prune", "step_other", "moment_stage", "diagnostics", "var_lstsq",
          "lag_transform", "fetch_idle")


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, value):
    return _field(1, key) + _field(2, value)


def test_op_paths_decode_string_and_reference_stats(tmp_path):
    """An XSpace with one device plane: a ``tf_op`` stat as a string, one
    as a reference to a stat-metadata name, one op without it; a line of
    events (skipped) and a host plane (not a device)."""
    plane = (_field(2, "/device:TPU:0")
             + _field(3, _field(2, "XLA Ops") + _field(4, _field(1, 1)))
             + _field(4, _entry(1, _field(1, 1) + _field(2, "%dot.1 = f32")
                                + _field(5, _field(1, 9) + _field(4, 12))
                                + _field(5, _field(1, 7) + _field(
                                    5, "jit(f)/lingam.prune/dot:"))))
             + _field(4, _entry(2, _field(1, 2) + _field(2, "%k.2 = f32")
                                + _field(5, _field(1, 7) + _field(7, 11))))
             + _field(4, _entry(3, _field(1, 3) + _field(2, "%copy.3")))
             + _field(5, _entry(7, _field(1, 7) + _field(2, "tf_op")))
             + _field(5, _entry(9, _field(1, 9) + _field(2, "flops")))
             + _field(5, _entry(11, _field(1, 11)
                                + _field(2, "jit(f)/lingam.moments/k:"))))
    host = _field(2, "/host:CPU") + _field(4, _entry(
        1, _field(1, 1) + _field(2, "lingam.fit")))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, host))
    assert xplane_scopes.op_paths(str(path)) == {"/device:TPU:0": {
        "%dot.1 = f32": "jit(f)/lingam.prune/dot:",
        "%k.2 = f32": "jit(f)/lingam.moments/k:",
    }}


@pytest.mark.parametrize("path,scope", [
    ("jit(_fit_local)/while/body/closed_call/lingam.moments/"
     "jit(pairwise_moments)/pallas_call:", "lingam.moments"),
    ("jit(f)/lingam.prune/while/body/lingam.moments/x:", "lingam.moments"),
    ("jit(_fit_local)/while/body/dynamic_update_slice:", "(unscoped)"),
    ("jit(var_lingam.py)/reduce_sum:", "(unscoped)"),
    (None, "(unscoped)"),
])
def test_an_op_goes_to_its_innermost_scope(path, scope):
    assert xplane_scopes.scope_of(path) == scope


@pytest.fixture(scope="module")
def scoped():
    return (xplane_scopes.reduce_file(SCOPED_TRACE),
            trace_reduce.reduce_file(SCOPED_TRACE))


@pytest.fixture(scope="module")
def unscoped():
    return (xplane_scopes.reduce_file(UNSCOPED_TRACE),
            trace_reduce.reduce_file(UNSCOPED_TRACE))


def test_every_kernel_event_has_a_moments_path():
    import jax

    paths = xplane_scopes.op_paths(SCOPED_TRACE)["/device:TPU:0"]
    pd = jax.profiler.ProfileData.from_file(SCOPED_TRACE)
    plane = next(p for p in pd.planes if p.name == "/device:TPU:0")
    ops = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    kernels = [e.name for e in ops.events
               if trace_reduce.KERNEL_MARK in e.name]
    assert len(kernels) == 64 + 48  # one per ordering step of each fit
    for name in kernels:
        assert name.startswith("%pairwise_moments_pallas"), name
        assert xplane_scopes.scope_of(paths[name]) == "lingam.moments"


@pytest.mark.parametrize("trace", ["scoped", "unscoped"])
def test_scope_times_sum_to_the_self_time_total(trace, request):
    by_scope, reduced = request.getfixturevalue(trace)
    total = sum(s for _, s in reduced["device_ops"])
    assert sum(by_scope["scope_s"].values()) == pytest.approx(total, rel=1e-9)
    assert total == pytest.approx(reduced["busy_s"], rel=1e-6)


def test_scoped_trace_reads_every_stage(scoped):
    by_scope, reduced = scoped
    stages = xplane_scopes.stage_ms(by_scope, 2)
    for name in STAGES:
        assert stages[name] is not None and stages[name] > 0.0, name
    # The kernel and its wrapper: more than the kernel alone.
    assert stages["moment_stage"] > 1e3 * reduced["kernel_s"] / 2
    # The stages hold the fit programs' time outside the kernel.
    fit_other = 1e3 * sum(
        reduced["modules"][m] - reduced["module_kernel_s"][m]
        for m, k in reduced["module_kernel_s"].items() if k > 0) / 2
    named = (stages["prune"] + stages["step_other"] + stages["diagnostics"]
             + stages["moment_stage"] - 1e3 * reduced["kernel_s"] / 2)
    # What no scope holds is the loops' own time (``%while`` ops carry no
    # path): a tenth here, at 64 and 48 steps of a few microseconds;
    # 0.4-0.8% of the cells' fits (PERF.md).
    assert named + stages["unscoped"] == pytest.approx(fit_other, rel=0.01)
    assert stages["unscoped"] < 0.1 * fit_other


def test_idle_time_inside_spans_is_at_most_the_idle_time(scoped):
    by_scope, reduced = scoped
    idle = reduced["window_s"] - reduced["busy_s"]
    spans = by_scope["idle_in_span_s"]
    assert set(spans) == {"lingam.fit", "lingam.fetch"}
    assert spans["lingam.fetch"] <= spans["lingam.fit"] <= idle + 1e-9


def test_unscoped_trace_reads_no_stage(unscoped):
    by_scope, reduced = unscoped
    assert set(by_scope["scope_s"]) == {"(unscoped)"}
    assert by_scope["idle_in_span_s"] == {}
    stages = xplane_scopes.stage_ms(by_scope, 1)
    assert all(stages[name] is None for name in STAGES)


def test_unscoped_trace_reduces_as_before(unscoped):
    """The committed trace's existing keys, as the benchmark's reduction
    read them before the stage names existed."""
    _, reduced = unscoped
    assert reduced["window_s"] == pytest.approx(0.005469509, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(0.002203788, rel=1e-9)
    assert reduced["kernel_s"] == pytest.approx(0.000414162, rel=1e-9)
    assert reduced["modules"] == pytest.approx(
        {"jit__fit_local": 0.002204235}, rel=1e-9)
    assert sum(s for _, s in reduced["idle_gaps"]) == pytest.approx(
        0.003265721, rel=1e-9)
