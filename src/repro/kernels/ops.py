"""Jit'd public wrappers around the pairwise-statistics kernels.

``pairwise_moments(x_std, c, backend=...)`` dispatches between:

  * ``"ref"``     — pure-jnp oracle (materializes (d, d, m); small shapes).
  * ``"blocked"`` — memory-bounded jnp fallback: lax.scan over row blocks.
                    This is also what the sharded/pjit path lowers, since
                    XLA fuses it well and it needs no pallas on CPU.
  * ``"pallas"``  — the Pallas TPU kernel (interpreted automatically when
                    no accelerator backs the process).

``pairwise_moments_masked(...)`` is the interventional variant (each
pair's moments over the samples valid for both variables), with the same
three backends.

All backends return (M1, M2) of shape (d, d) fp32 with identical values up
to fp32 accumulation tolerance; tests/test_kernels.py sweeps shapes/dtypes
against the oracle.

Every block-shape/variant decision in this module goes through the
autotuning dispatcher (:func:`repro.kernels.tune.dispatch`): ``backend``
``None`` lets the registry pick (pallas on accelerators, blocked
elsewhere), ``interpret`` ``None`` resolves to interpret-only-on-CPU,
``tune`` selects the dispatch mode (``"off"`` heuristic / ``"cache"`` /
``"auto"``), and ``plan`` pins an explicit
:class:`~repro.kernels.tune.registry.Plan` (the autotuner measuring a
candidate). Tuned and heuristic plans produce bit-identical moments —
see the parity contract on :mod:`repro.kernels.tune.registry`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from . import pairwise_stats, ref
from .nonlinearity import nonlinear_terms as _nonlinear_terms  # noqa: F401
from .tune import registry as tune

_DEFAULT_TUNE = "cache"


def _round_up(x: int, k: int) -> int:
    return ((x + k - 1) // k) * k


def pairwise_moments_blocked(x_std, c, block: int = 64, masked=None):
    """Row-blocked jnp implementation: O(block * d * m) peak memory.

    Scans over blocks of ``i`` rows; within a block the (block, d, m)
    residual tensor is formed and reduced. XLA fuses the nonlinearities
    into the reduction, so HBM traffic stays ~(d/block) * read(X).

    ``masked`` = ``(valid, alpha, gamma, n_pair)`` computes the masked
    moments of :func:`pairwise_moments_masked`: ``u = alpha (x_i - c x_j)
    + gamma``, each pair-sample weighed by both variables' validity, the
    sums divided by ``n_pair``.
    """
    m, d = x_std.shape
    block = min(block, _round_up(d, 8))  # don't pad tiny d up to a block
    d_pad = _round_up(d, block)

    def pad_rows(a):
        return jnp.pad(a.astype(jnp.float32), ((0, d_pad - d), (0, 0)))

    def pad_pairs(a):
        return jnp.pad(a.astype(jnp.float32),
                       ((0, d_pad - d), (0, d_pad - d)))

    xt = pad_rows(x_std.T)
    c_pad = pad_pairs(c)
    if masked is None:
        inv_std = jax.lax.rsqrt(jnp.maximum(1.0 - c_pad * c_pad, ref.EPS))
    else:
        valid, alpha, gamma, n_pair = masked
        vt = pad_rows(valid.T)
        inv_std = pad_pairs(alpha)
        gamma = pad_pairs(gamma)

    def body(_, idx):
        def rows(a):
            return jax.lax.dynamic_slice_in_dim(a, idx * block, block, 0)

        xi, ci, inv = rows(xt), rows(c_pad), rows(inv_std)
        r = xi[:, None, :] - ci[:, :, None] * xt[None, :, :]
        u = r * inv[:, :, None]
        if masked is None:
            logcosh, uexp = _nonlinear_terms(u)
            return None, (jnp.mean(logcosh, axis=-1), jnp.mean(uexp, axis=-1))
        logcosh, uexp = _nonlinear_terms(u + rows(gamma)[:, :, None])
        w = rows(vt)[:, None, :] * vt[None, :, :]
        return None, (jnp.sum(logcosh * w, axis=-1),
                      jnp.sum(uexp * w, axis=-1))

    _, (m1, m2) = jax.lax.scan(body, None, jnp.arange(d_pad // block))
    m1 = m1.reshape(d_pad, d_pad)[:d, :d]
    m2 = m2.reshape(d_pad, d_pad)[:d, :d]
    if masked is not None:
        m1, m2 = m1 / n_pair, m2 / n_pair
    return m1, m2


def _pallas_layout(plan, m, d):
    """A pair-tile Pallas plan's layout at (m, d): its column block, and
    the pads that lay an (m, d) array out variables-major and a (d, d)
    array on the same variable extent, both fp32 with zero padding."""
    d_pad, bj = tune.padded_extent(d, plan.bi, plan.bj)
    m_pad = _round_up(m, plan.bm)

    def pad_t(a):
        return jnp.pad(
            a.T.astype(jnp.float32), ((0, d_pad - d), (0, m_pad - m))
        )

    def pad_pairs(a):
        return jnp.pad(
            a.astype(jnp.float32), ((0, d_pad - d), (0, d_pad - d))
        )

    return bj, pad_t, pad_pairs


@functools.partial(
    jax.jit, static_argnames=("backend", "interpret", "block", "tune_mode",
                              "plan")
)
def pairwise_moments(
    x_std,
    c,
    *,
    backend: str = None,
    interpret: bool = None,
    block: int = None,
    tune_mode: str = _DEFAULT_TUNE,
    plan: tune.Plan = None,
):
    """Dispatching wrapper. x_std: (m, d) standardized; c: (d, d).

    Also accepts a leading batch axis — x_std: (b, m, d), c: (b, d, d) —
    and vmaps the selected backend over it, for callers batching at the
    kernel level rather than over whole fits. (The bootstrap/ensemble
    engine in ``repro.core.batched`` vmaps entire fits instead, so its
    traces reach this function with per-element 2-D shapes.)
    """
    if x_std.ndim == 3:
        return jax.vmap(
            lambda xb, cb: pairwise_moments(
                xb, cb, backend=backend, interpret=interpret, block=block,
                tune_mode=tune_mode, plan=plan,
            )
        )(x_std, c)
    m, d = x_std.shape
    if backend == "ref":
        return ref.pairwise_moments_ref(x_std, c)
    if plan is None:
        plan = tune.dispatch(
            "pairwise_moments", (m, d), str(x_std.dtype), backend,
            mode=tune_mode,
        )
    if plan.backend == "ref":
        return ref.pairwise_moments_ref(x_std, c)
    if plan.backend == "blocked":
        return pairwise_moments_blocked(x_std, c, block=block or plan.block)
    if plan.backend == "pallas":
        interpret = tune.resolve_interpret(interpret)
        bj, pad_t, pad_pairs = _pallas_layout(plan, m, d)
        m1, m2 = pairwise_stats.pairwise_moments_pallas(
            pad_t(x_std), pad_pairs(c), m_total=m, d_total=d, bi=plan.bi,
            bj=bj, bm=plan.bm, interpret=interpret,
        )
        return m1[:d, :d], m2[:d, :d]
    raise ValueError(f"unknown backend: {plan.backend}")


@functools.partial(
    jax.jit, static_argnames=("backend", "interpret", "tune_mode")
)
def pairwise_moments_masked(
    x_std,
    valid,
    c,
    alpha,
    gamma,
    n_pair,
    *,
    backend: str = None,
    interpret: bool = None,
    tune_mode: str = _DEFAULT_TUNE,
):
    """Masked pairwise residual moments, for interventional data.

    x_std: (m, d) standardized; valid: (m, d) 1.0 where the sample counts
    for the variable, else 0.0; c, alpha, gamma: (d, d), each pair's
    residual map ``u_ij = alpha_ij (x_i - c_ij x_j) + gamma_ij``; n_pair:
    (d, d) the count of samples valid for both. Returns (M1, M2), (d, d)
    fp32: the means of ``log cosh u`` and ``u exp(-u^2/2)`` over each
    pair's common valid samples. ``backend="ref"`` computes them from
    ``x_std`` and ``valid`` alone, by their definition
    (:func:`repro.kernels.ref.pairwise_moments_masked_ref`).
    """
    m, d = x_std.shape
    if backend == "ref":
        return ref.pairwise_moments_masked_ref(x_std, valid)
    plan = tune.dispatch(
        "pairwise_moments_masked", (m, d), str(x_std.dtype), backend,
        mode=tune_mode,
    )
    if plan.backend == "blocked":
        return pairwise_moments_blocked(
            x_std, c, block=plan.block,
            masked=(valid, alpha, gamma, n_pair),
        )
    if plan.backend != "pallas":
        raise ValueError(f"unknown backend: {plan.backend}")
    interpret = tune.resolve_interpret(interpret)
    bj, pad_t, pad_pairs = _pallas_layout(plan, m, d)
    s1, s2 = pairwise_stats.pairwise_moments_masked_pallas(
        pad_t(x_std), pad_t(valid), pad_pairs(c), pad_pairs(alpha),
        pad_pairs(gamma), m_total=m, d_total=d, bi=plan.bi, bj=bj,
        bm=plan.bm, interpret=interpret,
    )
    return s1[:d, :d] / n_pair, s2[:d, :d] / n_pair


def pairwise_moment_sums_rows(
    x_std,
    c,
    row_start,
    tile: int,
    *,
    chunk: int = 512,
    backend: str = None,
    interpret: bool = None,
    tune_mode: str = _DEFAULT_TUNE,
    plan: tune.Plan = None,
):
    """Pairwise residual moment *sums* for the i-row tile
    ``[row_start, row_start + tile)`` against all columns — the
    building block of the mesh execution plan.

    Args:
      x_std: (m_local, d) data standardized by *global* statistics.
             Rows past the valid sample count must be zeroed — both
             moment integrands vanish at 0, so zeroed rows contribute
             nothing to the sums.
      c:     (d, d) global correlation.
      row_start: traced scalar start of the row tile (a device's
             ``axis_index * tile`` under ``shard_map``).
      tile:  static tile height.
    Returns:
      (S1, S2): (tile, d) partial sums over the local sample rows — the
      caller psums over sample shards and divides by the global count.
      ``blocked`` scans over sample chunks (pure jnp); ``pallas`` runs
      the paper's kernel on the local slab (row-tile variant) — the
      kernel composed with ``shard_map`` is the full multi-pod
      configuration. Row-tile block shapes come from the dispatcher
      (``Partition.chunk`` bounds the sample block); non-divisible
      extents are zero-padded here and masked in the kernel.
    """
    m_local, d = x_std.shape
    if plan is None:
        plan = tune.dispatch(
            "pairwise_moment_sums_rows", (tile, d, m_local),
            str(x_std.dtype), backend, mode=tune_mode, chunk=chunk,
        )
    if plan.backend == "pallas":
        interpret = tune.resolve_interpret(interpret)
        bi, bm = plan.bi, plan.bm
        tile_pad = _round_up(tile, bi)
        # Pad variables and samples to block multiples: padded rows and
        # columns are sliced back off below, padded samples are masked
        # or skipped via m_total. The padded rows also hold a tile_pad
        # slice from any valid row_start (<= d - tile), so dynamic_slice
        # never clamps it.
        d_pad, bj = tune.padded_extent(d + tile_pad - tile, bi, plan.bj)
        m_pad = _round_up(m_local, bm)
        xt_all = jnp.pad(x_std.T, ((0, d_pad - d), (0, m_pad - m_local)))
        c_full = jnp.pad(c, ((0, d_pad - d), (0, d_pad - d)))
        xt_rows = jax.lax.dynamic_slice_in_dim(xt_all, row_start, tile_pad, 0)
        c_rows = jax.lax.dynamic_slice_in_dim(c_full, row_start, tile_pad, 0)
        s1, s2 = pairwise_stats.pairwise_moment_sums_rows(
            xt_rows, xt_all, c_rows, m_total=m_local,
            bi=bi, bj=bj, bm=bm, interpret=interpret,
        )
        return s1[:tile, :d], s2[:tile, :d]
    if plan.backend != "blocked":
        raise ValueError(f"unknown backend: {plan.backend}")
    xt = x_std.T  # (d, m_local)
    c_rows = jax.lax.dynamic_slice_in_dim(c, row_start, tile, 0)  # (tile, d)
    inv_std = jax.lax.rsqrt(jnp.maximum(1.0 - c_rows * c_rows, ref.EPS))

    m_pad = _round_up(m_local, chunk)
    xt = jnp.pad(xt, ((0, 0), (0, m_pad - m_local)))
    n_chunks = m_pad // chunk
    # Mask the padded tail inside the nonlinearities.
    base_valid = jnp.arange(m_pad) < m_local

    def body(carry, k):
        s1, s2 = carry
        xs = jax.lax.dynamic_slice_in_dim(xt, k * chunk, chunk, 1)  # (d, chunk)
        xi = jax.lax.dynamic_slice_in_dim(xs, row_start, tile, 0)   # (tile, chunk)
        valid = jax.lax.dynamic_slice_in_dim(base_valid, k * chunk, chunk, 0)
        r = xi[:, None, :] - c_rows[:, :, None] * xs[None, :, :]
        u = jnp.where(valid[None, None, :], r * inv_std[:, :, None], 0.0)
        logcosh, uexp = _nonlinear_terms(u)
        logcosh = jnp.where(valid[None, None, :], logcosh, 0.0)
        s1 = s1 + jnp.sum(logcosh, axis=-1)
        s2 = s2 + jnp.sum(uexp, axis=-1)
        return (s1, s2), None

    init = (
        jnp.zeros((tile, d), jnp.float32),
        jnp.zeros((tile, d), jnp.float32),
    )
    (s1, s2), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return s1, s2


def pairwise_moment_sums_chunked(
    x_std,
    c,
    *,
    chunk: int = 512,
    backend: str = None,
    interpret: bool = None,
    tune_mode: str = _DEFAULT_TUNE,
    plan: tune.Plan = None,
):
    """Pairwise residual moment *sums* accumulated over sample chunks.

    The streaming entry point: scans ``x_std`` in (chunk, d) sample
    slabs and accumulates the (d, d) moment sums of each slab via
    :func:`pairwise_moment_sums_rows` (the Pallas row-tile kernel for
    the pallas variant, the chunked jnp scan otherwise), so the peak
    residual intermediate is O(chunk * d^2) instead of O(m * d^2) — a
    rolling window's moments cost one chunk of live memory regardless
    of window length. ``chunk`` is the caller's memory bound and fixes
    the outer accumulation grouping; the dispatcher tunes the blocks
    *within* each slab (bit-identical by the parity contract).

    Args:
      x_std: (m, d) data standardized by the *window's* statistics.
      c:     (d, d) window correlation.
    Returns:
      (S1, S2): (d, d) fp32 sums over all m samples; divide by m for the
      means (:func:`pairwise_moments_chunked`). The sample axis is
      zero-padded to a chunk multiple — both integrands vanish at 0, so
      pad rows contribute nothing.
    """
    m, d = x_std.shape
    chunk = max(1, min(chunk, m))
    if plan is None:
        plan = tune.dispatch(
            "pairwise_moment_sums_chunked", (m, d), str(x_std.dtype),
            backend, mode=tune_mode, chunk=chunk,
        )
    inner_plan = dataclasses.replace(plan, op="pairwise_moment_sums_rows")
    if plan.backend != "pallas":
        # The row-tile entry already scans masked (chunk, d) slabs over
        # the full row range for the jnp backend.
        return pairwise_moment_sums_rows(
            x_std, c, 0, d, chunk=chunk, backend=plan.backend,
            interpret=interpret, plan=inner_plan,
        )
    # Pallas path: scan the row-tile kernel over chunk slabs; pad the
    # sample axis with zero rows (both integrands vanish at 0).
    m_pad = _round_up(m, chunk)
    x = jnp.pad(x_std.astype(jnp.float32), ((0, m_pad - m), (0, 0)))
    n_chunks = m_pad // chunk

    def body(carry, k):
        s1, s2 = carry
        xs = jax.lax.dynamic_slice_in_dim(x, k * chunk, chunk, 0)
        t1, t2 = pairwise_moment_sums_rows(
            xs, c, 0, d, chunk=chunk, backend=plan.backend,
            interpret=interpret, plan=inner_plan,
        )
        return (s1 + t1, s2 + t2), None

    init = (
        jnp.zeros((d, d), jnp.float32),
        jnp.zeros((d, d), jnp.float32),
    )
    (s1, s2), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    return s1, s2


@functools.partial(
    jax.jit, static_argnames=("chunk", "backend", "interpret", "tune_mode",
                              "plan")
)
def pairwise_moments_chunked(
    x_std,
    c,
    *,
    chunk: int = 512,
    backend: str = None,
    interpret: bool = None,
    tune_mode: str = _DEFAULT_TUNE,
    plan: tune.Plan = None,
):
    """Chunk-accumulated pairwise moment *means*: sums / m.

    Drop-in for :func:`pairwise_moments` with O(chunk)-bounded sample
    intermediates (``FitConfig.moment_chunk`` routes the local plan's
    ordering here). Agrees with the unchunked backends to fp32
    accumulation order.
    """
    m, _ = x_std.shape
    s1, s2 = pairwise_moment_sums_chunked(
        x_std, c, chunk=chunk, backend=backend, interpret=interpret,
        tune_mode=tune_mode, plan=plan,
    )
    inv_m = jnp.float32(1.0 / m)
    return s1 * inv_m, s2 * inv_m


def fused_moment_rows(
    x_raw,
    mu,
    rstd,
    c,
    row_start: int,
    tile: int,
    *,
    interpret: bool = None,
    tune_mode: str = _DEFAULT_TUNE,
    plan: tune.Plan = None,
):
    """Dispatcher-planned wrapper over the fused standardize+moments
    kernel (:func:`repro.kernels.fused_stats.fused_moment_sums`).

    Takes *raw* sample-major data plus the per-variable standardization
    constants, pads every extent to the plan's block multiples (padded
    samples are masked in the kernel; padded variables are sliced back
    off), and returns the (tile, d) moment *sums* for rows
    ``[row_start, row_start + tile)``. ``row_start`` is a host int here
    (the mesh path slices its tile before calling the kernel).
    """
    from .fused_stats import fused_moment_sums

    m, d = x_raw.shape
    if plan is None:
        plan = tune.dispatch(
            "fused_moment_sums", (tile, d, m), str(x_raw.dtype),
            "pallas", mode=tune_mode,
        )
    interpret = tune.resolve_interpret(interpret)
    bi, bm = plan.bi, plan.bm
    tile_pad = _round_up(tile, bi)
    # The row slice must fit inside the padded variable extent even when
    # the tile straddles the end of the real rows.
    d_pad, bj = tune.padded_extent(max(d, row_start + tile_pad), bi, plan.bj)
    m_pad = _round_up(m, bm)
    xt = jnp.pad(x_raw.T, ((0, d_pad - d), (0, m_pad - m)))
    # Per-variable constants as (d_pad, 1) columns: 2-D blocks that
    # Mosaic lays out like the data rows they scale.
    mu_pad = jnp.pad(mu.astype(jnp.float32), (0, d_pad - d))[:, None]
    rstd_pad = jnp.pad(rstd.astype(jnp.float32), (0, d_pad - d))[:, None]
    c_pad = jnp.pad(
        c.astype(jnp.float32), ((0, d_pad - d), (0, d_pad - d))
    )
    row_slice = slice(row_start, row_start + tile_pad)
    s1, s2 = fused_moment_sums(
        xt[row_slice], xt, mu_pad[row_slice], mu_pad,
        rstd_pad[row_slice], rstd_pad, c_pad[row_slice],
        m_total=m, bi=bi, bj=bj, bm=bm, interpret=interpret,
    )
    return s1[:tile, :d], s2[:tile, :d]


def standardize(x, eps=ref.EPS):
    """(m, d) -> standardized columns, ddof=0 (matches Algorithm 1)."""
    return ref.standardize(x, axis=0, eps=eps)


def correlation(x_std):
    return ref.correlation(x_std)
