"""Performance accounting: cost capture, memory watermarks and roofline
utilization.

This is the fourth telemetry primitive (after spans, metrics, and the
compile log): it answers *how close to the hardware* the compiled
programs run, not just how long they took.

  * **Cost capture** — :func:`call` routes a jitted entry point through
    the profiler: once per ``(op, shape-bucket, config-hash)`` signature
    it AOT-lowers the program and records ``cost_analysis()``
    FLOPs/bytes, ``memory_analysis()`` argument/output/temp watermarks,
    and per-collective operand bytes parsed from the optimized HLO
    (:func:`collective_bytes`). Signatures use the exact key scheme of
    :mod:`repro.obs.compile_log`, so cost rows and compile events join
    on ``(op, shape, config)``.
  * **Roofline utilization** — :func:`device_peaks` is a small registry
    of per-device peak FLOP/s and memory bandwidth (detected from
    ``jax.devices()[0].device_kind``; override with ``REPRO_PEAKS``).
    :func:`utilization` turns (flops, bytes, seconds) into achieved
    GFLOP/s, GB/s, arithmetic intensity, and fraction-of-roofline;
    every timed :func:`call` feeds these into ``obs.metrics`` gauges.

For the host spans and device ops on one timeline, run the work inside
``jax.profiler.trace``: every ``obs.span`` is then a profiler
annotation (:mod:`repro.obs.trace`).

Profiling is **off by default** — enable with :func:`enable` or
``REPRO_OBS_PROFILE=1``. Disabled, :func:`call` is a plain passthrough
(one flag test, no timing, no lowering), so results and compile counts
are bit-identical to un-instrumented runs — the same pinned guarantee
spans give. Enabled, calls are synchronous (``block_until_ready``) and
the first call per signature additionally AOT-compiles, so compile
events may double-fire; only the *disabled* state carries the
zero-delta pin.
"""

from __future__ import annotations

import dataclasses
import math
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from . import compile_log, metrics

_ENV_VAR = "REPRO_OBS_PROFILE"
_PEAKS_ENV = "REPRO_PEAKS"

_ENABLED = os.environ.get(_ENV_VAR, "").strip().lower() not in (
    "", "0", "false", "off",
)

_lock = threading.Lock()
_records: Dict[Tuple, "CostRecord"] = {}


def enable(on: bool = True) -> None:
    """Turn performance profiling on (cost capture + timed calls)."""
    global _ENABLED
    _ENABLED = bool(on)


def disable() -> None:
    enable(False)


def enabled() -> bool:
    return _ENABLED


def _trace_clean() -> bool:
    """True when no jax trace is active. Cost capture must never run
    mid-trace: lowering there would stage host work into someone else's
    program; inside a trace :func:`call` degrades to a plain call."""
    import jax

    return jax.core.trace_ctx.is_top_level()


# ---------------------------------------------------------------------------
# Device-peaks registry (roofline ceilings)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Peak rates of one device kind — the roofline ceilings.

    ``flops_per_s`` is the dense fp32 (or bf16 where that is the native
    matmul rate) peak of one chip; ``hbm_bw`` its main-memory bandwidth
    in bytes/s; ``ici_bw`` the per-link interconnect bandwidth used for
    collective terms. Entries are nominal vendor numbers — utilization
    fractions are comparative, not certified.
    """

    name: str
    flops_per_s: float
    hbm_bw: float
    ici_bw: float


#: Substring-matched (against ``device_kind.lower()``) peak entries,
#: first match wins; a device kind that matches none is an error. The
#: cpu entry is a deliberately round placeholder for a ~2-core
#: container — override with ``REPRO_PEAKS`` for real host baselines.
PEAKS_TABLE: Tuple[Tuple[str, DevicePeaks], ...] = (
    ("v5 lite", DevicePeaks("tpu-v5e", 197e12, 819e9, 50e9)),
    ("v5e", DevicePeaks("tpu-v5e", 197e12, 819e9, 50e9)),
    ("v5p", DevicePeaks("tpu-v5p", 459e12, 2765e9, 100e9)),
    ("v4", DevicePeaks("tpu-v4", 275e12, 1228e9, 50e9)),
    ("v3", DevicePeaks("tpu-v3", 123e12, 900e9, 50e9)),
    ("h100", DevicePeaks("gpu-h100", 989e12, 3350e9, 450e9)),
    ("a100", DevicePeaks("gpu-a100", 312e12, 2039e9, 300e9)),
    ("gpu", DevicePeaks("gpu-generic", 100e12, 1000e9, 100e9)),
    ("cpu", DevicePeaks("cpu-generic", 100e9, 20e9, 10e9)),
)


def device_peaks(kind: Optional[str] = None) -> DevicePeaks:
    """Roofline ceilings for ``kind`` (default: the process's device).

    Raises ``KeyError`` for a device kind missing from
    :data:`PEAKS_TABLE`: a roofline against invented peaks would be
    read as a measurement. ``REPRO_PEAKS`` overrides individual fields
    on top of the detected entry — ``REPRO_PEAKS="flops=3.2e12,hbm=80e9"``
    calibrates a real host without code changes (keys:
    name/flops/hbm/ici).
    """
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    low = str(kind).lower()
    base = next((p for token, p in PEAKS_TABLE if token in low), None)
    if base is None:
        raise KeyError(f"no roofline peaks for device kind {kind!r}")
    env = os.environ.get(_PEAKS_ENV, "").strip()
    if not env:
        return base
    fields = {"name": base.name, "flops": base.flops_per_s,
              "hbm": base.hbm_bw, "ici": base.ici_bw}
    for part in env.split(","):
        if "=" not in part:
            continue
        k, v = part.split("=", 1)
        k = k.strip().lower()
        if k == "name":
            fields["name"] = v.strip()
        elif k in fields:
            try:
                fields[k] = float(v)
            except ValueError:
                pass
    return DevicePeaks(
        name=str(fields["name"]), flops_per_s=float(fields["flops"]),
        hbm_bw=float(fields["hbm"]), ici_bw=float(fields["ici"]),
    )


def utilization(
    flops: float, bytes_accessed: float, seconds: float,
    peaks: Optional[DevicePeaks] = None,
) -> Dict[str, Any]:
    """Achieved rates and roofline fraction of one timed execution.

    ``roofline_frac`` is (roofline-bound seconds) / (measured seconds):
    the bound is ``max(flops/peak_flops, bytes/hbm_bw)``, so 1.0 means
    the kernel ran exactly at the ceiling its arithmetic intensity
    allows. Values above 1 flag a mis-calibrated peaks entry (cache
    effects on cpu commonly produce them) rather than magic hardware.
    """
    peaks = peaks or device_peaks()
    s = max(float(seconds), 1e-12)
    t_compute = flops / peaks.flops_per_s
    t_memory = bytes_accessed / peaks.hbm_bw
    bound_s = max(t_compute, t_memory)
    return {
        "gflops_per_s": flops / s / 1e9,
        "gbytes_per_s": bytes_accessed / s / 1e9,
        "intensity": flops / max(bytes_accessed, 1.0),
        "roofline_frac": bound_s / s,
        "bound": "compute" if t_compute >= t_memory else "memory",
        "peaks": peaks.name,
    }


# ---------------------------------------------------------------------------
# Analytic cost model (estimates from the shapes + the test oracle)
# ---------------------------------------------------------------------------

#: Flops per (pair, sample) element of the moment kernels' integrands:
#: residual u = x_i - c_ij * x_j (2), log cosh as |u| + log1p(exp(-2|u|))
#: - log2 (~19 counting each transcendental as 8), u * exp(-u^2/2)
#: (~12), two fp32 accumulates (2) — 35 total. A *model*, not an HLO
#: count: it makes analytic and measured rows comparable, and the
#: roofline-oracle test pins the arithmetic below against it.
PAIR_FLOPS = 35


def analytic_cost(op: str, shape) -> Optional[Dict[str, float]]:
    """Model FLOPs/bytes for one registered moment op at one shape.

    Byte counts are the streamed-traffic model (fp32): each input slab
    read once per use, both (d, d)-family moment outputs written once —
    the same working-set accounting as ``registry.vmem_bytes``. Returns
    None for ops without a model.
    """
    try:
        dims = tuple(int(s) for s in shape)
    except TypeError:
        return None
    if op == "pairwise_moments" and len(dims) == 2:
        m, d = dims
        flops = float(PAIR_FLOPS) * d * d * m
        nbytes = 4.0 * (2 * m * d + 2 * d * d)
    elif op in ("pairwise_moment_sums_rows", "fused_moment_sums") \
            and len(dims) == 3:
        tile, d, m = dims
        flops = float(PAIR_FLOPS) * tile * d * m
        nbytes = 4.0 * (m * tile + m * d + 2 * tile * d)
    elif op == "pairwise_moment_sums_chunked" and len(dims) == 2:
        m, d = dims
        flops = float(PAIR_FLOPS) * d * d * m
        nbytes = 4.0 * (2 * m * d + 2 * d * d)
    else:
        return None
    return {
        "flops": flops,
        "bytes": nbytes,
        "intensity": flops / max(nbytes, 1.0),
    }


# ---------------------------------------------------------------------------
# HLO collective-bytes parser (moved from analysis/roofline.py)
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# one shaped buffer: f32[128,256]  (layout braces optional)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(.+)$")
_OPND_RE = re.compile(r"\(([^)]*)\)")


def _shape_bytes(text: str) -> int:
    """Sum bytes over all shaped buffers appearing in ``text``."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind operand bytes (per device) from optimized HLO.

    ``cost_analysis()`` does not attribute collective traffic, so this
    parses the post-partitioning module (``compiled.as_text()``): build
    a name->bytes table from every instruction's result shape, then sum
    the operand sizes of each all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute.
    """
    sizes: Dict[str, int] = {}
    pending = []
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        # result type = shapes in rhs before the opcode's '('.
        head = rhs.split("(", 1)[0]
        sizes[name.lstrip("%")] = _shape_bytes(head)
        for kind in _COLLECTIVES:
            # match opcode token, e.g. " all-reduce(" or "all-reduce-start("
            if re.search(rf"\b{kind}(-start)?\(", rhs):
                pending.append((kind, rhs))
                break

    out = {k: 0 for k in _COLLECTIVES}
    for kind, rhs in pending:
        opnds = _OPND_RE.search(rhs)
        got = 0
        if opnds:
            for op in opnds.group(1).split(","):
                op = op.strip().lstrip("%")
                # operands may be written 'f32[..] %name' or just '%name'
                tok = op.split(" ")[-1].lstrip("%")
                if tok in sizes:
                    got += sizes[tok]
                else:
                    got += _shape_bytes(op)
        if got == 0:
            got = _shape_bytes(rhs.split("(", 1)[0])  # fallback: result
        out[kind] += got
    return out


# ---------------------------------------------------------------------------
# Cost store
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CostRecord:
    """One program signature's captured costs + execution statistics."""

    op: str
    shape: Tuple[int, ...]
    config: str                      # compile_log.config_hash token
    flops: float = 0.0               # per-execution, from cost_analysis
    bytes_accessed: float = 0.0
    arg_bytes: int = 0               # memory_analysis watermarks
    out_bytes: int = 0
    temp_bytes: int = 0
    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)
    source: str = "measured"         # "measured" | "analytic" | "unavailable"
    calls: int = 0
    total_s: float = 0.0
    best_s: float = math.inf

    def row(self, peaks: Optional[DevicePeaks] = None) -> Dict[str, Any]:
        """JSON-safe row with utilization derived at the best latency."""
        out: Dict[str, Any] = {
            "op": self.op,
            "shape": list(self.shape),
            "config": self.config,
            "source": self.source,
            "flops": self.flops,
            "bytes": self.bytes_accessed,
            "arg_bytes": self.arg_bytes,
            "out_bytes": self.out_bytes,
            "temp_bytes": self.temp_bytes,
            "collective_bytes": dict(self.collectives),
            "calls": self.calls,
            "total_s": self.total_s,
            "best_s": self.best_s if self.calls else 0.0,
        }
        if self.calls and (self.flops or self.bytes_accessed):
            out.update(utilization(
                self.flops, self.bytes_accessed, self.best_s, peaks
            ))
        return out


def _key(op: str, shape, config) -> Tuple:
    # The exact compile_log key scheme: cost rows join compile events.
    return (op, compile_log._shape_key(shape), compile_log.config_hash(config))


def _capture(fn, args, kwargs, op: str, shape, config) -> CostRecord:
    rec = CostRecord(
        op=op,
        shape=compile_log._shape_key(shape),
        config=compile_log.config_hash(config),
    )
    compiled = None
    try:
        compiled = fn.lower(*args, **kwargs).compile()
    except Exception:
        compiled = None
    if compiled is None:
        a = analytic_cost(op, shape)
        if a is not None:
            rec.flops = a["flops"]
            rec.bytes_accessed = a["bytes"]
            rec.source = "analytic"
        else:
            rec.source = "unavailable"
        return rec
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):  # one entry per executable
            cost = cost[0] if cost else {}
        rec.flops = float(cost.get("flops", 0.0) or 0.0)
        rec.bytes_accessed = float(cost.get("bytes accessed", 0.0) or 0.0)
    except Exception:
        a = analytic_cost(op, shape)
        if a is not None:
            rec.flops, rec.bytes_accessed = a["flops"], a["bytes"]
            rec.source = "analytic"
    try:
        mem = compiled.memory_analysis()
        rec.arg_bytes = int(getattr(mem, "argument_size_in_bytes", 0) or 0)
        rec.out_bytes = int(getattr(mem, "output_size_in_bytes", 0) or 0)
        rec.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
    except Exception:
        pass
    try:
        coll = collective_bytes(compiled.as_text())
        rec.collectives = {k: v for k, v in coll.items() if v}
    except Exception:
        pass
    return rec


def call(fn, *args, op: str, shape=None, config=None, **kwargs):
    """Route one jitted entry-point call through the profiler.

    Disabled (the default), this is ``fn(*args, **kwargs)`` — no timing,
    no lowering, bit-identical results and compile counts. Enabled, the
    first call per ``(op, shape-bucket, config-hash)`` captures costs
    via the AOT path (:func:`CostRecord`), then every call is timed
    synchronously and folded into the record plus ``obs.metrics``
    gauges. Mid-trace calls always pass straight through.
    """
    if not _ENABLED or not _trace_clean():
        return fn(*args, **kwargs)
    key = _key(op, shape, config)
    with _lock:
        rec = _records.get(key)
    if rec is None:
        rec = _capture(fn, args, kwargs, op, shape, config)
        with _lock:
            rec = _records.setdefault(key, rec)
    import jax

    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    out = jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    with _lock:
        rec.calls += 1
        rec.total_s += dt
        rec.best_s = min(rec.best_s, dt)
    metrics.observe(f"profile.{op}_s", dt)
    if rec.flops or rec.bytes_accessed:
        u = utilization(rec.flops, rec.bytes_accessed, dt)
        metrics.gauge("profile.gflops_per_s", u["gflops_per_s"], op=op)
        metrics.gauge("profile.gbytes_per_s", u["gbytes_per_s"], op=op)
        metrics.gauge("profile.roofline_frac", u["roofline_frac"], op=op)
    if rec.temp_bytes:
        metrics.gauge("profile.temp_bytes", rec.temp_bytes, op=op)
    return out


def records() -> List[CostRecord]:
    """Every captured record (insertion order)."""
    with _lock:
        return list(_records.values())


def get(op: str, shape=None, config=None) -> Optional[CostRecord]:
    """The record for one signature, or None."""
    with _lock:
        return _records.get(_key(op, shape, config))


def snapshot() -> Dict[str, Any]:
    """JSON-safe dump: device peaks + one row per captured signature."""
    peaks = device_peaks()
    return {
        "device": dataclasses.asdict(peaks),
        "records": [r.row(peaks) for r in records()],
    }


def reset() -> None:
    """Drop every captured cost record (tests / fresh windows)."""
    with _lock:
        _records.clear()
