"""Kernel autotuning & dispatch subsystem.

The paper's speed-ups come from hand-tuned kernels; ParaLiNGAM shows the
*scheduling* — which variant runs where, with what block shape —
dominates parallel LiNGAM performance. This package replaces every
static block-shape decision in the repo with one measured, cached,
dispatched subsystem:

  * :mod:`registry <repro.kernels.tune.registry>` — a
    :class:`~repro.kernels.tune.registry.KernelVariant` registry
    wrapping the Pallas pair-tile / row-tile kernels, the fused
    standardize+moments kernel, the blocked jnp fallback and the
    chunked wrappers behind one
    :func:`~repro.kernels.tune.registry.dispatch` interface with
    declared constraints (sublane/lane alignment, the VMEM working-set
    model, sample-axis accumulation granularity, mesh compatibility).
  * :mod:`autotune <repro.kernels.tune.autotune>` — an aligned,
    VMEM-bounded candidate generator plus a timed search harness that
    benchmarks candidates per ``(device_kind, op, shape-bucket,
    dtype)`` and emits a :class:`~repro.kernels.tune.autotune.TunePlan`.
  * :mod:`cache <repro.kernels.tune.cache>` — the persistent JSON
    tuning table (repo-committed ``default_plans.json`` + an overlay
    file, read only when ``$REPRO_TUNE_CACHE`` names one) with shape
    bucketing and versioned keys, so serving and streaming sessions hit
    tuned plans without a first-request search.

Modes (``FitConfig.tune`` / ``dispatch(mode=...)``): ``"off"`` is the
deterministic offline fallback (pure heuristic, no filesystem),
``"cache"`` (default) reads the table and never measures, ``"auto"``
runs the timed search once per bucket and persists the winner. Tuned
and heuristic plans are bit-identical in output — block shapes re-tile
the pair space and the kernels accumulate samples in fixed 128-wide
sub-chunks, so only speed changes (``tests/test_tune.py`` pins this;
``benchmarks/bench_tune.py`` reports heuristic-vs-tuned timings per
bucket into ``BENCH_kernels.json``).
"""

from . import cache, registry  # noqa: F401
from .cache import TuneTable, get_table, plan_key, reset_table, shape_bucket  # noqa: F401
from .registry import (  # noqa: F401
    ACCUM_CHUNK,
    Constraints,
    KernelVariant,
    Plan,
    default_backend,
    default_interpret,
    dispatch,
    dispatch_heuristic,
    get_variant,
    resolve_interpret,
    vmem_bytes,
)

__all__ = [
    "ACCUM_CHUNK",
    "Constraints",
    "KernelVariant",
    "Plan",
    "TuneTable",
    "autotune",
    "cache",
    "default_backend",
    "default_interpret",
    "dispatch",
    "dispatch_heuristic",
    "get_table",
    "get_variant",
    "plan_key",
    "registry",
    "reset_table",
    "resolve_interpret",
    "shape_bucket",
    "vmem_bytes",
]


def __getattr__(name):
    # Lazy: autotune drives the ops wrappers, which import this package
    # (importlib, not ``from . import`` — the latter re-enters this hook).
    if name == "autotune":
        import importlib

        mod = importlib.import_module(".autotune", __name__)
        globals()["autotune"] = mod
        return mod
    raise AttributeError(name)
