"""The fit's stage names, on the device and on the host.

Each stage of a fit runs under a ``jax.named_scope`` named
``lingam.<stage>``: the name lands in the ``op_name`` metadata of the
stage's ops, which a profiler trace keeps per device op. The facades'
host spans (``lingam.fit``, ``lingam.fetch``) are profiler annotations
while a profiler session is active, and the shared no-op otherwise.
"""

import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import DirectLiNGAM, VarLiNGAM, api, var_lingam
from repro.data.simulate import simulate_lingam
from repro.obs import trace

ORDERING = ("standardize", "moments", "scores", "residual")
FINISH = ("prune", "diagnostics")


@pytest.fixture(autouse=True)
def _telemetry_off():
    obs.disable()
    obs.reset_all()
    yield
    obs.reset_all()


def _scopes(text: str) -> set:
    """The ``lingam.<stage>`` names in a lowered program's debug text."""
    return set(re.findall(r"(?<![\w.])lingam\.([a-z_]+)(?=[/\"])", text))


def _sds(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


@pytest.mark.parametrize("compaction", ["none", "staged"])
def test_fit_program_names_every_stage(compaction):
    cfg = api.FitConfig(compaction=compaction)
    text = api._fit_local.lower(_sds(96, 12), cfg).as_text(debug_info=True)
    expected = set(ORDERING + FINISH)
    if compaction == "staged":
        expected.add("compact")
    assert _scopes(text) == expected


@pytest.mark.parametrize("program,args,scope", [
    (lambda x: var_lingam.estimate_var(x, lags=2), [(96, 5)], "var_regress"),
    (var_lingam.lag_transform, [(5, 5), (2, 5, 5)], "lag_transform"),
])
def test_var_programs_name_their_stage(program, args, scope):
    """The VAR regression and the lag transform are one program each,
    under one scope."""
    lowered = jax.jit(program).lower(*[_sds(*s) for s in args])
    assert _scopes(lowered.as_text(debug_info=True)) == {scope}


def test_span_without_profiler_session_is_the_shared_noop():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    s = obs.span("lingam.fit", d=3)
    assert s is obs.span("lingam.fetch") is trace._NOOP
    with s as entered:
        assert entered.set(d=4) is entered


def _host_events(log_dir):
    (path,) = glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.end_ns)
            for plane in pd.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("facade", ["DirectLiNGAM", "VarLiNGAM"])
@pytest.mark.parametrize("telemetry", [False, True])
def test_fit_under_profiler_writes_its_host_spans(tmp_path, facade,
                                                  telemetry):
    """Inside ``jax.profiler.trace`` the facade's spans are host events
    of the trace, telemetry on or off: ``lingam.fetch`` (the host reads)
    inside ``lingam.fit``."""
    x = simulate_lingam(m=200, d=5, seed=0).data
    model = (DirectLiNGAM(compaction="staged") if facade == "DirectLiNGAM"
             else VarLiNGAM(lags=1, compaction="staged"))
    model.fit(x)  # compile outside the trace
    if telemetry:
        obs.enable()
    with jax.profiler.trace(str(tmp_path)):
        with obs.span("outer") as outer:
            outer.set(note="attributes are not carried")
            model.fit(x)
    events = _host_events(str(tmp_path))
    (fit,) = [e for e in events if e[0] == "lingam.fit"]
    (fetch,) = [e for e in events if e[0] == "lingam.fetch"]
    (outer,) = [e for e in events if e[0] == "outer"]
    assert outer[1] <= fit[1] <= fetch[1] <= fetch[2] <= fit[2] <= outer[2]
    if telemetry:
        (root,) = obs.roots()
        assert [c.name for c in root.children] == ["lingam.fit"]
    else:
        assert obs.roots() == []
