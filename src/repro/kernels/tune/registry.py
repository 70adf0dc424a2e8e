"""Kernel variant registry + the single block-shape decision point.

Every moment-kernel entry point in the repo (the Pallas pair-tile and
row-tile kernels, the fused standardize+moments kernel, the blocked jnp
fallback and the chunked wrappers) is wrapped here as a
:class:`KernelVariant` with declared constraints — sublane/lane
alignment, the VMEM working-set model, sample-axis accumulation
granularity, mesh compatibility. :func:`dispatch` is the **only** place
a ``(bi, bj, bm)`` / row-block decision is made: the wrappers in
``repro.kernels.ops`` (and through them the local, vmap, mesh, and
stream execution plans) all ask it for a :class:`Plan`.

Resolution order inside ``dispatch``:

  1. explicit ``plan`` overrides win (the autotuner measuring a
     candidate, a test pinning a shape);
  2. with ``mode="cache"`` (default) or ``"auto"``, the persistent
     tuning table (:mod:`repro.kernels.tune.cache`) is consulted under
     the versioned ``(device_kind, op, dtype, shape-bucket)`` key; a hit
     is validated against the variant's constraints for the *actual*
     shape before use;
  3. ``mode="auto"`` runs the timed search on a miss (once per bucket,
     persisted to the user overlay);
  4. otherwise — and always for ``mode="off"`` — the deterministic
     heuristic (the old ``ops._pick_blocks`` logic, folded in here).

**Bit-parity contract.** Tuned and heuristic plans for the same op
produce bit-identical moment outputs: block shapes only re-tile the
(i, j) pair space (per-element arithmetic untouched; pairs past the
valid extents are skipped, not computed), and the kernels accumulate the
sample axis in fixed :data:`ACCUM_CHUNK`-wide sub-chunks, in sample
order, so any ``bm`` that is a multiple of ``ACCUM_CHUNK`` yields the
same fp32 reduction order (padded samples are masked or skipped). The
candidate generator only emits such ``bm``; ``tests/test_tune.py`` pins
the parity.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Dict, Optional, Tuple

from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.obs import trace as obs_trace

from . import cache as tune_cache

#: Sample-axis accumulation granularity shared with the Pallas kernels
#: (``pairwise_stats`` / ``fused_stats``): any bm that is a multiple of
#: this produces a bit-identical reduction order (lane width, fp32).
ACCUM_CHUNK = 128

_SUBLANE = 8      # fp32 second-to-last-dim tile
_LANE = 128       # last-dim tile / VPU lane width
#: v5e's default scoped-VMEM limit (16 MiB) less headroom for Mosaic's
#: own scratch; see vmem_bytes().
_VMEM_BUDGET = 14 * 1024 * 1024

_MODES = ("off", "cache", "auto")


def _round_up(x: int, k: int) -> int:
    return ((x + k - 1) // k) * k


def _trace_state_clean() -> bool:
    """True when no jax trace is active. The timed search must not run
    mid-trace: the candidate runs execute eagerly there, but the wall
    times absorb tracing overhead and would persist distorted plans —
    inside a trace, ``mode="auto"`` degrades to the heuristic and the
    search is deferred to an eager dispatch point (engine warm-up, the
    bench harness, a direct ops call)."""
    import jax

    return jax.core.trace_ctx.is_top_level()


def vmem_bytes(bi: int, bj: int, bm: int) -> int:
    """fp32 VMEM working set of one (BI, BJ, BM) grid cell of the moment
    kernels (``pairwise_stats.moment_sums``): the double-buffered input
    blocks (x_i, x_j, correlations) and output blocks, and the residual
    and integrand tensors of the (BI, BJ, ACCUM_CHUNK) sample chunks the
    kernel forms one by one. Mosaic keeps about ten such chunk tensors
    live (compiled for v5e: 24.3 MB at (32, 128, 2048), 36.0 MB at
    (64, 128, 1024)); the model counts twelve."""
    return 4 * (
        2 * (bi * bm + bj * bm + bi * bj)   # inputs, double-buffered
        + 2 * 2 * bi * bj                   # two outputs, double-buffered
        + 12 * bi * bj * ACCUM_CHUNK        # live chunk tensors
    )


def lane_block(d: int) -> int:
    """Column block for ``d`` pair columns: one 128-lane tile when the
    columns span more than one, else every column (padded to a sublane
    multiple) in a single block."""
    return _LANE if d > _LANE else _round_up(max(d, 1), _SUBLANE)


def padded_extent(d: int, bi: int, bj: int) -> Tuple[int, int]:
    """``(d_pad, bj)``: the padded pair-column extent and the column
    block the TPU accepts for it.

    Mosaic takes a block only when its last dimension is a multiple of
    the 128-lane tile or the whole array extent (and its second-to-last
    a multiple of 8 or the whole extent). A lane-multiple ``bj`` tiles
    the columns padded up to it and to the row block ``bi``; any other
    ``bj`` — the plans for at most 128 columns — becomes one block
    spanning all columns, padded to a multiple of ``bi``.
    """
    if bj % _LANE == 0:
        return _round_up(d, math.lcm(bi, bj)), bj
    d_pad = _round_up(d, bi)
    return d_pad, d_pad


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """Pallas interpreter only when no accelerator backs the process —
    real hardware must never silently run interpret mode."""
    import jax

    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


@functools.lru_cache(maxsize=1)
def default_backend() -> str:
    """Backend when the caller does not force one: the Pallas kernels on
    an accelerator, the blocked jnp fallback elsewhere."""
    return "pallas" if not default_interpret() else "blocked"


@functools.lru_cache(maxsize=1)
def device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


@dataclasses.dataclass(frozen=True)
class Constraints:
    """Declared execution constraints of one kernel variant."""

    sublane: int = _SUBLANE        # bi (and bj) alignment quantum
    lane: int = _LANE              # preferred bj / bm alignment
    accum_chunk: int = ACCUM_CHUNK  # bm granularity for bit-stable sums
    vmem_budget: int = _VMEM_BUDGET  # working-set bound for candidates
    mesh_compatible: bool = True   # usable inside shard_map row tiles
    tunable: Tuple[str, ...] = ()  # which Plan fields the search may vary


@dataclasses.dataclass(frozen=True)
class Plan:
    """One block-shape/variant decision. Hashable (jit-static) and
    serializable (tuning table rows are its dict form)."""

    op: str
    variant: str
    backend: str
    bi: int = 0
    bj: int = 0
    bm: int = 0
    block: int = 0      # row block of the blocked jnp backend
    source: str = "heuristic"  # "heuristic" | "tuned" | "override"

    def to_entry(self) -> dict:
        return {
            "variant": self.variant,
            "backend": self.backend,
            "bi": self.bi,
            "bj": self.bj,
            "bm": self.bm,
            "block": self.block,
        }

    @classmethod
    def from_entry(cls, op: str, entry: dict) -> "Plan":
        return cls(
            op=op,
            variant=str(entry.get("variant", "")),
            backend=str(entry.get("backend", "")),
            bi=int(entry.get("bi", 0)),
            bj=int(entry.get("bj", 0)),
            bm=int(entry.get("bm", 0)),
            block=int(entry.get("block", 0)),
            source="tuned",
        )


@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """A registered kernel entry point with its constraints and its
    deterministic fallback plan."""

    name: str
    op: str
    backend: str
    constraints: Constraints
    heuristic: Callable[..., Plan]  # (shape, chunk) -> Plan
    validate: Callable[..., bool]   # (plan, shape, chunk) -> bool


REGISTRY: Dict[Tuple[str, str], KernelVariant] = {}


def register(variant: KernelVariant) -> KernelVariant:
    key = (variant.op, variant.backend)
    if key in REGISTRY:
        raise ValueError(f"duplicate kernel variant for {key}")
    REGISTRY[key] = variant
    return variant


def get_variant(op: str, backend: str) -> KernelVariant:
    try:
        return REGISTRY[(op, backend)]
    except KeyError:
        raise ValueError(
            f"no kernel variant registered for op={op!r} "
            f"backend={backend!r}; known: {sorted(REGISTRY)}"
        ) from None


# ---------------------------------------------------------------------------
# Heuristics (the old static decisions, folded into the fallback path)
# ---------------------------------------------------------------------------


def heuristic_pair_blocks(d: int, m: int) -> Tuple[int, int, int]:
    """Lane-legal pair-tile block shapes, VMEM-bounded (:func:`vmem_bytes`).

    ``bj`` is one 128-lane tile, or every column when there are at most
    128 (:func:`lane_block`): a wider column block pads the pair extent
    further at the staged widths. The kernel is straight-line code over
    the ``bm / 128`` sample chunks of a block, so ``bm`` trades grid
    steps against compile time: 4,096 ran the 4,096 x 964 kernel 5%
    faster on a v5e than 2,048, but the staged fit, which compiles one
    kernel per stage width, then takes minutes to compile.
    """
    bi, bj = _SUBLANE, lane_block(d)
    if m >= 4096:
        bm = 2048
    elif m >= 512:
        bm = 512
    else:
        bm = 256
    return bi, bj, bm


def _pair_pallas_heuristic(shape, chunk=None) -> Plan:
    m, d = shape
    bi, bj, bm = heuristic_pair_blocks(d, m)
    return Plan(
        op="pairwise_moments", variant="pallas-pair-tile",
        backend="pallas", bi=bi, bj=bj, bm=bm,
    )


def _pair_blocked_heuristic(shape, chunk=None) -> Plan:
    m, d = shape
    block = min(64, _round_up(max(d, 1), _SUBLANE))
    return Plan(
        op="pairwise_moments", variant="blocked-rows",
        backend="blocked", block=block,
    )


def _rows_pallas_heuristic(shape, chunk=None) -> Plan:
    tile, d, m = shape
    # The sample block is the caller's chunk when that is lane-aligned
    # and tiles m, else the whole sample extent in ACCUM_CHUNK chunks.
    bm = (chunk if chunk and chunk % _LANE == 0 and m % chunk == 0
          else _round_up(max(m, 1), ACCUM_CHUNK))
    return Plan(
        op="pairwise_moment_sums_rows", variant="pallas-row-tile",
        backend="pallas", bi=_SUBLANE, bj=lane_block(d), bm=bm,
    )


def _rows_blocked_heuristic(shape, chunk=None) -> Plan:
    # chunk is the caller's memory bound (Partition.chunk / stream
    # chunk); the jnp scan grouping follows it, so it is not tunable —
    # re-grouping would break the chunk-count-invariant sums.
    return Plan(
        op="pairwise_moment_sums_rows", variant="rows-chunked-jnp",
        backend="blocked", bm=int(chunk or 512),
    )


def _chunked_heuristic(backend, name):
    def h(shape, chunk=None) -> Plan:
        m, d = shape
        inner = dispatch_heuristic(
            "pairwise_moment_sums_rows", (d, d, int(chunk or 512)),
            backend=backend, chunk=chunk,
        )
        return dataclasses.replace(
            inner, op="pairwise_moment_sums_chunked", variant=name,
        )
    return h


def _fused_pallas_heuristic(shape, chunk=None) -> Plan:
    tile, d, m = shape
    return Plan(
        op="fused_moment_sums", variant="pallas-fused",
        backend="pallas", bi=_SUBLANE, bj=lane_block(d),
        bm=512 if m >= 512 else 256,
    )


def _validate_pallas(plan: Plan, shape, chunk=None) -> bool:
    """A tuned Pallas plan is admissible for this shape when the TPU
    accepts its blocks (rows a sublane multiple; columns a lane
    multiple, or one block over at most 128 columns — see
    :func:`padded_extent`), it pads the pair extent no further than the
    heuristic (``bi`` divides 128; ``bj`` one 128-lane tile over more than
    128 columns), it is bit-stable (bm a multiple of the accumulation
    chunk), its working set fits the VMEM budget, and it is within the
    chunk memory bound when one applies. Divisibility is *not* required —
    the ops wrappers pad to the plan's blocks. ``shape[1]`` is d for
    every pair op."""
    if plan.bi < 1 or plan.bj < 1 or plan.bm < 1:
        return False
    if plan.bi % _SUBLANE or _LANE % plan.bi:
        return False
    if shape[1] > _LANE and plan.bj != _LANE:
        return False
    if plan.bm % ACCUM_CHUNK:
        return False
    if vmem_bytes(plan.bi, plan.bj, plan.bm) > _VMEM_BUDGET:
        return False
    if chunk and plan.bm > chunk:
        return False
    return True


def _validate_blocked(plan: Plan, shape, chunk=None) -> bool:
    return plan.block >= 1 and plan.block % _SUBLANE == 0


def _validate_fixed(plan: Plan, shape, chunk=None) -> bool:
    return False  # nothing tunable: heuristic only


register(KernelVariant(
    name="pallas-pair-tile",
    op="pairwise_moments",
    backend="pallas",
    constraints=Constraints(
        mesh_compatible=False, tunable=("bi", "bj", "bm")
    ),
    heuristic=_pair_pallas_heuristic,
    validate=_validate_pallas,
))
register(KernelVariant(
    name="blocked-rows",
    op="pairwise_moments",
    backend="blocked",
    constraints=Constraints(tunable=("block",)),
    heuristic=_pair_blocked_heuristic,
    validate=_validate_blocked,
))
register(KernelVariant(
    name="ref-oracle",
    op="pairwise_moments",
    backend="ref",
    constraints=Constraints(mesh_compatible=False, tunable=()),
    heuristic=lambda shape, chunk=None: Plan(
        op="pairwise_moments", variant="ref-oracle", backend="ref"
    ),
    validate=_validate_fixed,
))
register(KernelVariant(
    name="pallas-row-tile",
    op="pairwise_moment_sums_rows",
    backend="pallas",
    constraints=Constraints(tunable=("bi", "bj", "bm")),
    heuristic=_rows_pallas_heuristic,
    validate=_validate_pallas,
))
register(KernelVariant(
    name="rows-chunked-jnp",
    op="pairwise_moment_sums_rows",
    backend="blocked",
    constraints=Constraints(tunable=()),
    heuristic=_rows_blocked_heuristic,
    validate=_validate_fixed,
))
register(KernelVariant(
    name="chunked-pallas-row-tile",
    op="pairwise_moment_sums_chunked",
    backend="pallas",
    constraints=Constraints(tunable=("bi", "bj")),
    heuristic=_chunked_heuristic("pallas", "chunked-pallas-row-tile"),
    validate=_validate_pallas,
))
register(KernelVariant(
    name="chunked-rows-jnp",
    op="pairwise_moment_sums_chunked",
    backend="blocked",
    constraints=Constraints(tunable=()),
    heuristic=_chunked_heuristic("blocked", "chunked-rows-jnp"),
    validate=_validate_fixed,
))
register(KernelVariant(
    name="pallas-fused",
    op="fused_moment_sums",
    backend="pallas",
    constraints=Constraints(tunable=("bi", "bj", "bm")),
    heuristic=_fused_pallas_heuristic,
    validate=_validate_pallas,
))


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------


def dispatch_heuristic(
    op: str, shape, *, backend: Optional[str] = None, chunk: Optional[int] = None
) -> Plan:
    """The deterministic fallback plan (no table, no measurement)."""
    backend = backend or default_backend()
    return get_variant(op, backend).heuristic(shape, chunk)


def dispatch(
    op: str,
    shape,
    dtype: str = "float32",
    backend: Optional[str] = None,
    *,
    mode: str = "cache",
    chunk: Optional[int] = None,
    mesh: bool = False,
    table: Optional[tune_cache.TuneTable] = None,
) -> Plan:
    """The single block-shape/variant decision point.

    Args:
      op:     registered op name ("pairwise_moments",
              "pairwise_moment_sums_rows", "pairwise_moment_sums_chunked",
              "fused_moment_sums").
      shape:  static dispatch shape — (m, d) for the pair ops,
              (tile, d, m) for the row/fused ops. Called at trace time,
              where these are Python ints.
      dtype:  input dtype token (part of the tuning key).
      backend: force a backend ("blocked"/"pallas"/"ref"); None lets the
              registry pick (pallas on accelerators, blocked otherwise).
      mode:   "off" (heuristic, deterministic — the offline mode),
              "cache" (tuned table lookup, heuristic fallback; never
              measures), "auto" (search + persist on a miss).
      chunk:  caller's sample-chunk memory bound, when one applies.
      mesh:   require a mesh-compatible (shard_map-safe) variant.
      table:  explicit :class:`TuneTable` (tests/benchmarks); defaults
              to the process singleton.
    """
    with obs_trace.span(
        "kernels.dispatch", op=op, shape=tuple(shape), mode=mode
    ) as sp:
        plan = _dispatch_resolve(
            op, shape, dtype, backend,
            mode=mode, chunk=chunk, mesh=mesh, table=table,
        )
        sp.set(variant=plan.variant, source=plan.source)
    # Per-variant dispatch counts + tuned-vs-heuristic plan provenance
    # (off unless telemetry is enabled; dispatch runs at trace time, so
    # steady-state traffic never reaches this).
    obs_metrics.inc(
        "kernels.dispatch",
        op=op, backend=plan.backend, variant=plan.variant,
        source=plan.source,
    )
    # Profiling on: the decision's analytic cost model + VMEM working
    # set become gauges next to the measured cost records, so a plan
    # whose model disagrees with captured temp_bytes is visible.
    obs_profile.note_plan(
        op, shape, variant=plan.variant, source=plan.source,
        vmem_model_bytes=(
            vmem_bytes(plan.bi, plan.bj, plan.bm)
            if plan.backend == "pallas" and plan.bi else 0
        ),
    )
    return plan


def _dispatch_resolve(
    op: str,
    shape,
    dtype: str = "float32",
    backend: Optional[str] = None,
    *,
    mode: str = "cache",
    chunk: Optional[int] = None,
    mesh: bool = False,
    table: Optional[tune_cache.TuneTable] = None,
) -> Plan:
    if mode not in _MODES:
        raise ValueError(f"unknown tune mode {mode!r}; expected {_MODES}")
    backend = backend or default_backend()
    variant = get_variant(op, backend)
    if mesh and not variant.constraints.mesh_compatible:
        raise ValueError(
            f"variant {variant.name!r} is not mesh-compatible "
            f"(op={op!r}, backend={backend!r})"
        )
    if mode == "off" or not variant.constraints.tunable:
        return variant.heuristic(shape, chunk)

    tbl = table if table is not None else tune_cache.get_table()
    key = tune_cache.plan_key(
        device_kind(), op, backend, dtype, tune_cache.shape_bucket(op, shape)
    )
    entry = tbl.lookup(key)
    if entry is not None:
        plan = Plan.from_entry(op, entry)
        if plan.backend == backend and variant.validate(plan, shape, chunk):
            return plan
        # A recorded plan that fails validation for this shape degrades
        # to the heuristic — deterministically, with no re-search loop.
        return variant.heuristic(shape, chunk)
    if mode == "auto" and not tbl.offline and _trace_state_clean():
        from . import autotune  # lazy: autotune drives the ops wrappers

        tuned = autotune.autotune_op(
            op, shape, dtype=dtype, backend=backend, chunk=chunk, table=tbl
        )
        return tuned.best
    return variant.heuristic(shape, chunk)
