"""Unified telemetry for the AcceleratedLiNGAM stack.

Three jit-safe primitives, wired through every layer of the repo:

  * :mod:`repro.obs.trace` — nested host-side spans
    (``with obs.span("ordering.step", d=d): ...``). Off by default;
    enable with :func:`enable` or ``REPRO_OBS=1``. Spans never stage
    anything into traced programs: instrumented and uninstrumented runs
    produce bit-identical results and identical compile counts. Inside
    ``jax.profiler.trace`` every span is also a profiler annotation of
    the same name, on the device trace's clock.
  * :mod:`repro.obs.metrics` — process-local counters / gauges /
    histograms with p50/p95/p99 summaries, exported via
    :func:`repro.obs.metrics.snapshot` or
    :func:`repro.obs.metrics.to_prometheus_text`.
  * :mod:`repro.obs.compile_log` — always-on compile-event accounting
    keyed by ``(op, shape, config_hash)``: every library jit entry point
    records its trace body, so recompile storms are queryable (and the
    test suite pins one-compile-per-bucket invariants through this
    public API instead of private counters).
  * :mod:`repro.obs.profile` — performance accounting on top of the
    other three: per-program ``cost_analysis()`` FLOPs/bytes and
    ``memory_analysis()`` watermarks keyed like the compile log,
    and roofline utilization against the device-peaks registry.
    Off by default; enable with :func:`repro.obs.profile.enable` or
    ``REPRO_OBS_PROFILE=1``.

``analysis/regress.py`` closes the loop: it compares fresh benchmark
runs against the committed ``BENCH_*.json`` baselines (stamped with
:func:`provenance`) and fails CI on out-of-tolerance slowdowns.
"""

from __future__ import annotations

import time
from typing import Any, Dict

from . import compile_log, metrics, profile, ring, trace
from .ring import BoundedRing  # noqa: F401
from .trace import (  # noqa: F401  (re-exported convenience surface)
    enable,
    disable,
    enabled,
    format_tree,
    reset,
    roots,
    span,
)

__all__ = [
    "BoundedRing",
    "compile_log",
    "metrics",
    "profile",
    "ring",
    "trace",
    "enable",
    "disable",
    "enabled",
    "format_tree",
    "provenance",
    "reset",
    "reset_all",
    "roots",
    "span",
]


def reset_all() -> None:
    """Clear spans, metrics, the compile log, and cost records."""
    trace.reset()
    metrics.reset()
    compile_log.reset()
    profile.reset()


def provenance(repo_root: str = ".") -> Dict[str, Any]:
    """What produced this process's numbers: device, versions, git sha.

    Stamped into every ``BENCH_*.json`` artifact by ``benchmarks/run.py``
    so regression comparisons know what hardware/runtime produced the
    baseline they are diffing against.
    """
    out: Dict[str, Any] = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    try:
        import platform

        out["python"] = platform.python_version()
        out["hostname"] = platform.node()
    except Exception:  # pragma: no cover
        pass
    try:
        import jax

        out["jax_version"] = jax.__version__
        out["device_kind"] = jax.devices()[0].device_kind
        out["backend"] = jax.default_backend()
        out["platform"] = jax.devices()[0].platform
        out["n_devices"] = jax.device_count()
    except Exception:  # pragma: no cover - jax must not be a hard dep here
        out["jax_version"] = out["device_kind"] = "unknown"
    import os as _os
    import platform as _platform

    out["machine"] = _platform.machine()
    out["xla_flags"] = _os.environ.get("XLA_FLAGS", "")
    try:
        import subprocess

        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=repo_root, capture_output=True, text=True, timeout=5,
        )
        out["git_sha"] = sha.stdout.strip() if sha.returncode == 0 else "unknown"
    except Exception:  # pragma: no cover
        out["git_sha"] = "unknown"
    return out
