"""Timed block-shape search: candidates -> measurements -> TunePlan.

The candidate generator emits the ``(BI, BJ, BM)`` plans the kernel
variant admits, bounded by the VMEM working-set model documented on
:func:`repro.kernels.tune.registry.vmem_bytes`; every sample-axis
block is a multiple of :data:`~repro.kernels.tune.registry.ACCUM_CHUNK`
so all candidates share one fp32 reduction order — tuned plans are
bit-identical to the heuristic, just faster. The search harness times
each candidate on synthetic data per ``(device_kind, op, shape-bucket,
dtype)`` through the *real* ops wrappers (explicit ``plan=`` override,
so dispatch is bypassed, not re-entered) and emits a :class:`TunePlan`;
the winning plan is recorded into the persistent tuning table
(:mod:`repro.kernels.tune.cache`) for ``dispatch(mode="cache")`` to hit
without ever measuring again.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.obs import profile as obs_profile

from . import cache as tune_cache
from . import registry

_BI_GRID = (8, 16, 32, 64)  # sublane multiples that divide 128
_BM_GRID = (128, 256, 512, 1024, 2048)


@dataclasses.dataclass
class Measurement:
    plan: registry.Plan
    seconds: float


@dataclasses.dataclass
class TunePlan:
    """One bucket's measured tuning decision."""

    key: str
    op: str
    dtype: str
    backend: str
    device_kind: str
    shape: Tuple[int, ...]
    best: registry.Plan
    measurements: List[Measurement]

    def to_row(self) -> dict:
        """JSON row for BENCH_kernels.json: the decision plus, when the
        analytic cost model covers this op, each candidate's achieved
        GFLOP/s and roofline fraction against the device-peaks registry
        — and the VMEM working-set model of every Pallas candidate, so
        the tuning table doubles as the model-validation artifact."""
        best_s = min(m.seconds for m in self.measurements)
        cost = obs_profile.analytic_cost(self.op, self.shape)
        peaks = obs_profile.device_peaks(self.device_kind)

        def cand_row(m: "Measurement") -> dict:
            row = {**m.plan.to_entry(), "us": m.seconds * 1e6}
            if m.plan.backend == "pallas" and m.plan.bi:
                row["vmem_model_bytes"] = registry.vmem_bytes(
                    m.plan.bi, m.plan.bj, m.plan.bm
                )
            if cost is not None:
                u = obs_profile.utilization(
                    cost["flops"], cost["bytes"], m.seconds, peaks
                )
                row["gflops_per_s"] = u["gflops_per_s"]
                row["roofline_frac"] = u["roofline_frac"]
            return row

        row = {
            "key": self.key,
            "op": self.op,
            "dtype": self.dtype,
            "backend": self.backend,
            "device_kind": self.device_kind,
            "shape": list(self.shape),
            "best": self.best.to_entry(),
            "best_us": best_s * 1e6,
            "candidates": [cand_row(m) for m in self.measurements],
        }
        if cost is not None:
            u = obs_profile.utilization(
                cost["flops"], cost["bytes"], best_s, peaks
            )
            row["flops"] = cost["flops"]
            row["bytes"] = cost["bytes"]
            row["gflops_per_s"] = u["gflops_per_s"]
            row["roofline_frac"] = u["roofline_frac"]
            row["bound"] = u["bound"]
        return row


def candidate_plans(
    op: str,
    shape,
    *,
    backend: Optional[str] = None,
    chunk: Optional[int] = None,
    quick: bool = False,
) -> List[registry.Plan]:
    """Aligned, VMEM-bounded, bit-stable candidate grid for one op: the
    plans the variant's ``validate`` admits (for the Pallas kernels:
    ``bj`` one 128-lane tile, ``bi`` dividing 128, ``bm`` a multiple of
    ``ACCUM_CHUNK``, the working set of
    :func:`~repro.kernels.tune.registry.vmem_bytes` within the budget).

    The heuristic plan is always included (dedup'd), so a tuned plan is
    never slower than the fallback the search replaces.
    """
    backend = backend or registry.default_backend()
    variant = registry.get_variant(op, backend)
    cons = variant.constraints
    heur = variant.heuristic(shape, chunk)
    plans: List[registry.Plan] = [heur]
    seen = {(heur.bi, heur.bj, heur.bm, heur.block)}

    def add(**kw):
        p = dataclasses.replace(heur, source="candidate", **kw)
        sig = (p.bi, p.bj, p.bm, p.block)
        if sig in seen or not variant.validate(p, shape, chunk):
            return
        seen.add(sig)
        plans.append(p)

    tunable = set(cons.tunable)
    # One column block: a 128-lane tile, or every column up to 128 — a
    # wider block pads the pair extent further at the staged widths.
    bj = registry.lane_block(shape[1])  # d for every pair op
    bi_grid = _BI_GRID[:1] if quick else _BI_GRID
    if tunable >= {"bi", "bj", "bm"}:
        m_axis = shape[0] if len(shape) == 2 else shape[2]
        bm_grid = [
            bm for bm in (_BM_GRID[:2] if quick else _BM_GRID)
            if bm <= registry._round_up(m_axis, cons.accum_chunk)
        ]
        for bi in bi_grid:
            for bm in bm_grid:
                add(bi=bi, bj=bj, bm=bm)
    elif tunable == {"bi", "bj"}:
        for bi in bi_grid:
            add(bi=bi, bj=bj)
    elif tunable == {"block"}:
        d = shape[1]
        cap = registry._round_up(max(d, 1), cons.sublane)
        for block in (8, 32, 64, 128):
            add(block=min(block, cap))
    return plans


def _bench_inputs(op: str, shape, dtype: str, seed: int = 0):
    """Synthetic standardized inputs for one op's timing run."""
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    if len(shape) == 2:
        m, d = shape
    else:
        _, d, m = shape
    x = rng.laplace(size=(m, d)).astype(np.float32)
    xs = ops.standardize(jnp.asarray(x))
    c = ops.correlation(xs)
    return jnp.asarray(x), xs, c


def _bench_fn(op: str, shape, dtype: str, interpret: Optional[bool], chunk):
    """Build ``run(plan) -> result`` for one op (inputs built once; each
    plan times one *compiled* program — the jitted closure per plan is
    memoized so repeats hit the XLA cache, and the untimed warm-up in
    :func:`measure_plan` absorbs the compile)."""
    import functools as _ft

    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    x_raw, xs, c = _bench_inputs(op, shape, dtype)

    if op == "pairwise_moments":
        def make(plan):
            return lambda: ops.pairwise_moments(
                xs, c, backend=plan.backend, interpret=interpret, plan=plan
            )
    elif op == "pairwise_moment_sums_rows":
        tile = shape[0]

        def make(plan):
            f = jax.jit(lambda a, b: ops.pairwise_moment_sums_rows(
                a, b, 0, tile, chunk=chunk or 512,
                backend=plan.backend, interpret=interpret, plan=plan,
            ))
            return lambda: f(xs, c)
    elif op == "pairwise_moment_sums_chunked":
        def make(plan):
            return lambda: ops.pairwise_moments_chunked(
                xs, c, chunk=chunk or 512,
                backend=plan.backend, interpret=interpret, plan=plan,
            )
    elif op == "fused_moment_sums":
        tile = shape[0]
        mu = jnp.mean(x_raw, axis=0)
        rstd = 1.0 / jnp.maximum(jnp.std(x_raw, axis=0), 1e-12)

        def make(plan):
            f = jax.jit(lambda a, b: ops.fused_moment_rows(
                a, mu, rstd, b, 0, tile, interpret=interpret, plan=plan,
            ))
            return lambda: f(x_raw, c)
    else:
        raise ValueError(f"no benchmark runner for op {op!r}")

    make = _ft.lru_cache(maxsize=None)(make)

    def timed(plan):
        return jax.block_until_ready(make(plan)())

    return timed


def measure_plan(run, plan, *, repeats: int = 3) -> float:
    """Min-of-repeats wall time (one untimed warm-up absorbs compile)."""
    run(plan)
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        run(plan)
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_op(
    op: str,
    shape,
    *,
    dtype: str = "float32",
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    chunk: Optional[int] = None,
    repeats: int = 3,
    quick: bool = False,
    table: Optional[tune_cache.TuneTable] = None,
    persist: bool = True,
) -> TunePlan:
    """Benchmark the candidate grid for one (op, shape) and record the
    winner in the tuning table under its bucketed key."""
    backend = backend or registry.default_backend()
    interpret = registry.resolve_interpret(interpret)
    cands = candidate_plans(
        op, shape, backend=backend, chunk=chunk, quick=quick
    )
    run = _bench_fn(op, shape, dtype, interpret, chunk)
    measurements = [
        Measurement(plan=p, seconds=measure_plan(run, p, repeats=repeats))
        for p in cands
    ]
    best = min(measurements, key=lambda m: m.seconds).plan
    best = dataclasses.replace(best, source="tuned")
    key = tune_cache.plan_key(
        registry.device_kind(), op, backend, dtype,
        tune_cache.shape_bucket(op, shape),
    )
    tuned = TunePlan(
        key=key,
        op=op,
        dtype=dtype,
        backend=backend,
        device_kind=registry.device_kind(),
        shape=tuple(shape),
        best=best,
        measurements=measurements,
    )
    tbl = table if table is not None else tune_cache.get_table()
    if not tbl.offline:
        entry = best.to_entry()
        entry["time_us"] = min(m.seconds for m in measurements) * 1e6
        tbl.record(key, entry, persist=persist)
    return tuned


def warmup_plans(
    shapes: Sequence[Tuple[int, int]],
    *,
    ops: Sequence[str] = ("pairwise_moments",),
    backend: Optional[str] = None,
    mode: str = "cache",
    chunk: Optional[int] = None,
    table: Optional[tune_cache.TuneTable] = None,
) -> Dict[str, registry.Plan]:
    """Resolve (and, with ``mode="auto"``, measure + persist) the plans
    for the (m, d) dataset shapes a serving/streaming engine expects —
    the warm-up hook ``serve.CausalDiscoveryEngine.warmup`` calls so
    first requests never pay a search."""
    out: Dict[str, registry.Plan] = {}
    for (m, d) in shapes:
        for op in ops:
            shape = (m, d) if op in (
                "pairwise_moments", "pairwise_moment_sums_chunked"
            ) else (d, d, m)
            # Mirror the fit path's clamp (ops.pairwise_moment_sums_chunked
            # bounds chunk by the sample count) so warm-up resolves the
            # same plan the first request will ask for.
            chunk_eff = max(1, min(chunk, m)) if chunk else chunk
            plan = registry.dispatch(
                op, shape, backend=backend, mode=mode, chunk=chunk_eff,
                table=table,
            )
            key = tune_cache.plan_key(
                registry.device_kind(), op, plan.backend, "float32",
                tune_cache.shape_bucket(op, shape),
            )
            out[key] = plan
    return out
