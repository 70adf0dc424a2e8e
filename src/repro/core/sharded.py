"""Mesh execution plan (shard_map) — the scale-out extension.

The paper parallelizes Algorithm 1 within one GPU. This module is the
**mesh** plan of the shared ordering step
(:func:`repro.core.ordering.ordering_step`): it contains *no* estimator
math of its own — scores, entropies, moment integrands, the residual
update, compaction schedules, and pruning all come from
:mod:`repro.core.ordering`, :mod:`repro.core.measures`,
:mod:`repro.kernels.ops`, and :mod:`repro.core.pruning`. What lives here
is only the :class:`MeshReducer` (how the step's reductions execute on a
device mesh) and the ``shard_map`` plumbing:

  * samples are sharded over the ``data`` (and ``pod``) mesh axes — every
    moment in the algorithm is a mean over samples, so shards reduce with
    a single ``psum`` (this is the DP-style axis; scales with m),
  * the (i, j) pair space is tiled over the ``model`` axis — each device
    computes the moment rows for its i-tile only (the Pallas row-tile
    kernel or its jnp fallback via ``ops.pairwise_moment_sums_rows``;
    TP-style axis; scales with d^2),

giving the hybrid sample x pair decomposition analysed in EXPERIMENTS.md
§Perf. Collectives per ordering step:
    psum(C)            : d^2            fp32 over data(+pod)
    psum(M1,M2 tiles)  : 2 d^2/|model|  fp32 over data(+pod)
    all_gather(M rows) : 2 d^2          fp32 over model
Everything else (scores, argmax, rank-1 residual update) is replicated
O(d^2) arithmetic.

:func:`fit_sharded` compiles the *full* fit — ordering (with in-trace
staged compaction when configured: stage widths stay multiples of the
pair-axis size, every shard gathers the same surviving columns) followed
by adjacency/pruning with the per-variable solves tiled over the pair
axis, and residual diagnostics — as one ``shard_map`` program returning
the same :class:`~repro.core.api.FitResult` pytree as the local plan.
The finish has two modes (``Partition.gather_finish``): the default
reassembles the data per device and reduces the covariance in a fixed
replicated order — bit-identical leaves at the parity cells
``tests/test_mesh_fit.py`` pins, fp32-ulp agreement in general — while
``gather_finish=False`` keeps the finish fully sharded (psum-reduced
covariance, local-row diagnostics) so per-device memory stays
O(m_local * d + d^2) end to end.

Variables are padded to a multiple of the pair-axis size and samples to
a multiple of (sample shards x chunk); padded columns enter with
``active=False`` so they never influence scores or updates, and padded
sample rows are zeroed so they drop out of every moment sum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.kernels.ops import _round_up
from repro.obs import compile_log
from repro.obs import profile as obs_profile
from . import measures, ordering, pruning
from .api import FitConfig, FitResult


class MeshReducer:
    """Mesh reduction plan: psum over sample shards, row tiles + all_gather
    over the pair axis. Implements the Reducer interface documented on
    :class:`repro.core.ordering.LocalReducer`; must be constructed inside
    the ``shard_map`` trace (it reads ``axis_index``).
    """

    def __init__(
        self,
        *,
        m: int,
        m_local: int,
        axis_sizes,
        sample_axes=("data",),
        pair_axis: str = "model",
        chunk: int = 512,
        backend: str = None,
        interpret: bool = None,
        fused_standardize: bool = False,
    ):
        self.m = m
        self.sample_axes = tuple(sample_axes)
        self.pair_axis = pair_axis
        self.n_pair = int(axis_sizes[pair_axis])
        self.col_multiple = self.n_pair
        self.chunk = chunk
        self.backend = backend
        self.interpret = interpret
        self.fused_standardize = fused_standardize

        # Which local rows are real samples: rows are distributed evenly
        # over the sample shards (this shard's block starts at
        # shard_id * m_local); the zero-padded tail lives on the last
        # shard(s).
        shard_id = jnp.int32(0)
        for ax in self.sample_axes:
            shard_id = shard_id * int(axis_sizes[ax]) + jax.lax.axis_index(ax)
        row_ids = shard_id * m_local + jnp.arange(m_local)
        self.valid_rows = (row_ids < m)[:, None]  # (m_local, 1)

    def mean_over_samples(self, v):
        """Global sample mean of local rows (padded rows are zero, so the
        local sums are exact sums over real rows)."""
        return jax.lax.psum(jnp.sum(v, axis=0), self.sample_axes) / self.m

    def gram_mean(self, v):
        return jax.lax.psum(v.T @ v, self.sample_axes) / self.m

    def mask_rows(self, v):
        # Padded sample rows must stay exactly zero *after* centering,
        # so mask them instead of shifting them to -mu.
        return jnp.where(self.valid_rows, v, 0.0)

    def standardize(self, x):
        if not self.fused_standardize:
            return ordering.step_standardize(x, self)
        # §Perf C2: correlation from the raw-X matmul + affine fold
        # C = D (G/m - mu mu^T) D with G = X^T X, D = diag(rstd) —
        # skips one standardized-slab matmul pass per step (padded
        # rows are zeros, so raw second moments are exact). The affine
        # fold is one-pass by construction (that is the trick); the
        # variance itself stays two-pass like the shared path.
        mu = self.mean_over_samples(x)
        xc = self.mask_rows(x - mu[None, :])
        var = jnp.maximum(self.mean_over_samples(xc * xc), ordering.EPS)
        rstd = jax.lax.rsqrt(var)
        x_std = xc * rstd[None, :]
        g = self.gram_mean(x)
        c = (g - mu[:, None] * mu[None, :]) * (rstd[:, None] * rstd[None, :])
        return x_std, c, mu, var

    def moment_rows(self, x_std, c):
        """This device's i-row tile of the pairwise residual moments."""
        tile = x_std.shape[1] // self.n_pair
        row_start = jax.lax.axis_index(self.pair_axis) * tile
        s1, s2 = ops.pairwise_moment_sums_rows(
            x_std, c, row_start, tile,
            chunk=self.chunk, backend=self.backend, interpret=self.interpret,
        )
        s1 = jax.lax.psum(s1, self.sample_axes) / self.m
        s2 = jax.lax.psum(s2, self.sample_axes) / self.m
        return s1, s2

    def gather_rows(self, rows):
        return jax.lax.all_gather(rows, self.pair_axis, axis=0, tiled=True)

    def col_moments(self, x_std):
        # Padded rows are exactly zero and both integrands vanish at 0,
        # so plain sums + /m are exact (logcosh re-masked for safety
        # against constant-folding differences).
        logcosh, uexp = measures.nonlinear_terms(x_std)
        logcosh = jnp.where(self.valid_rows, logcosh, 0.0)
        cm1 = jax.lax.psum(jnp.sum(logcosh, axis=0), self.sample_axes) / self.m
        cm2 = jax.lax.psum(jnp.sum(uexp, axis=0), self.sample_axes) / self.m
        return cm1, cm2

    def residual_coef(self, x, xr, root, mu, var):
        return ordering.step_residual_coef(x, xr, root, mu, var, self)

    def take_columns(self, idx):
        return self

    def gather_samples(self, x_local):
        """Reassemble the full (m_pad, width) array from sample shards
        (exact: a gather moves bits, it does not reduce)."""
        x_full = x_local
        for ax in reversed(self.sample_axes):  # minor axis first
            x_full = jax.lax.all_gather(x_full, ax, axis=0, tiled=True)
        return x_full


def _order_sharded(x_local, d, config: FitConfig, reducer: MeshReducer):
    """The configured ordering schedule on the mesh plan."""
    if config.compaction == "none":
        return ordering.masked_order_impl(x_local, reducer, d=d)
    if config.compaction == "staged":
        return ordering.compact_order_impl(
            x_local, reducer, d=d,
            frac=config.compaction_frac, min_stage=config.min_stage,
        )
    raise ValueError(f"unknown compaction: {config.compaction}")


def _pair_row_tiles(reducer: MeshReducer, order, d: int):
    """Row-tiling helpers for the pair axis: (mask_rows, rows_of, gather).

    The row dimension is padded so every device owns an equal tile;
    padded rows have all-False masks and solve to exactly zero before
    ``gather`` slices them back off.
    """
    n_pair = reducer.n_pair
    d_rows = _round_up(d, n_pair)
    row_tile = d_rows // n_pair
    row_start = jax.lax.axis_index(reducer.pair_axis) * row_tile

    def rows_of(full):
        padded = jnp.pad(full, ((0, d_rows - d), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(padded, row_start, row_tile, 0)

    def gather(rows):
        return jax.lax.all_gather(
            rows, reducer.pair_axis, axis=0, tiled=True
        )[:d]

    return rows_of(pruning.pred_mask(order)), rows_of, gather


def _finish_sharded(x, order, config: FitConfig, reducer: MeshReducer):
    """Bit-exact finish (``gather_finish=True``): adjacency +
    diagnostics on the reassembled data, row solves tiled over the pair
    axis.

    Mirrors :func:`repro.core.api.finish_fit` computation-for-computation:
    the covariance is reduced replicated (fixed reduction order) and
    each variable's masked OLS solve — row-independent given that
    covariance — runs on the device owning its row tile via the shared
    ``pruning.ols_rows``. The adaptive-lasso refinement runs replicated
    through the shared ``pruning`` entry point instead: its FISTA
    iterations are batched matvecs whose reduction lowering depends on
    the batch size, so a row tile would drift from the local plan by
    ulps over the 400 iterations — and it is part of the ~4% tail
    anyway. (Batched ``linalg.solve`` lowering can also differ by batch
    size at some shapes; the parity tests pin the cells where the OLS
    tiles are exact, and elsewhere the tiles agree to ulps.)
    """
    m, d = x.shape
    mask_rows, rows_of, gather = _pair_row_tiles(reducer, order, d)

    with jax.named_scope("lingam.prune"):
        if config.prune_method == "ols":
            xc = x - jnp.mean(x, axis=0, keepdims=True)
            cov = (xc.T @ xc) / m
            b = gather(pruning.ols_rows(cov, mask_rows, rows_of(cov)))
        elif config.prune_method == "adaptive_lasso":
            b = pruning.adaptive_lasso_adjacency(
                x, order, **config.prune_kwargs_dict
            )
        else:
            raise ValueError(f"unknown method: {config.prune_method}")
        b = pruning.apply_threshold(b, config.prune_threshold)
    with jax.named_scope("lingam.diagnostics"):
        xc0 = x - jnp.mean(x, axis=0, keepdims=True)
        resid = xc0 - xc0 @ b.T
        resid_var = jnp.mean(resid * resid, axis=0)
    return b, resid_var


def _finish_sharded_scaled(
    x_local, order, config: FitConfig, reducer: MeshReducer, d: int
):
    """Fully sharded finish (``gather_finish=False``): the dataset is
    never reassembled — the covariance/correlation are psum-reduced over
    sample shards, solves run on pair-axis row tiles, and the residual
    diagnostics stay on local rows. Per-device memory is
    O(m_local * d + d^2), the scale regime the ordering already runs in;
    coefficients agree with the gathered finish to fp32 reduction order.
    """
    x = x_local[:, :d]
    mask_rows, rows_of, gather = _pair_row_tiles(reducer, order, d)

    with jax.named_scope("lingam.prune"):
        mu = reducer.mean_over_samples(x)
        xc = reducer.mask_rows(x - mu[None, :])
        cov = reducer.gram_mean(xc)

        if config.prune_method == "ols":
            b = gather(pruning.ols_rows(cov, mask_rows, rows_of(cov)))
        elif config.prune_method == "adaptive_lasso":
            kw = config.prune_kwargs_dict
            lam = kw.get("lam", 0.01)
            gamma = kw.get("gamma", 1.0)
            n_steps = kw.get("n_steps", 400)
            var = reducer.mean_over_samples(xc * xc)
            sd = jnp.maximum(jnp.sqrt(var), 1e-12)
            corr = reducer.gram_mean(xc / sd[None, :])
            b_ols = gather(pruning.ols_rows(cov, mask_rows, rows_of(cov)))
            b_ols_std = b_ols * (sd[None, :] / sd[:, None])
            w = 1.0 / jnp.maximum(jnp.abs(b_ols_std), 1e-3) ** gamma
            lip = jnp.float32(d)
            b_std = gather(pruning.lasso_rows(
                corr, mask_rows, rows_of(corr), rows_of(w), lam, lip, n_steps
            ))
            b = b_std * (sd[:, None] / sd[None, :])
        else:
            raise ValueError(f"unknown method: {config.prune_method}")
        b = pruning.apply_threshold(b, config.prune_threshold)
    with jax.named_scope("lingam.diagnostics"):
        resid = xc - xc @ b.T  # local rows; padded rows are zero -> 0
        resid_var = reducer.mean_over_samples(resid * resid)
    return b, resid_var


@functools.lru_cache(maxsize=None)
def _build_sharded_fit(m: int, d: int, config: FitConfig):
    """Compile-cached sharded full-fit program for one (m, d) shape.

    Returns (jitted_fn, m_pad, d_pad); call with (m_pad, d_pad) data.
    """
    from repro.launch.mesh import mesh_from_spec

    part = config.partition
    mesh = mesh_from_spec(part.mesh)
    axis_sizes = dict(part.mesh)
    n_sample_shards = 1
    for ax in part.sample_axes:
        n_sample_shards *= axis_sizes[ax]
    n_pair = axis_sizes[part.pair_axis]

    m_pad = _round_up(m, n_sample_shards * part.chunk)
    d_pad = _round_up(d, n_pair)
    m_local = m_pad // n_sample_shards

    def full_fit(x_local):
        compile_log.record(
            "sharded.fit", shape=(m, d), config=config,
            mesh="x".join(str(s) for _, s in part.mesh),
        )
        reducer = MeshReducer(
            m=m, m_local=m_local, axis_sizes=axis_sizes,
            sample_axes=part.sample_axes, pair_axis=part.pair_axis,
            chunk=part.chunk, backend=config.backend,
            interpret=config.interpret,
            fused_standardize=part.fused_standardize,
        )
        order = _order_sharded(x_local, d, config, reducer)
        # The ~4% tail: bit-exact on reassembled data, or fully sharded.
        if part.gather_finish:
            x_full = reducer.gather_samples(x_local)[:m, :d]
            b, resid_var = _finish_sharded(x_full, order, config, reducer)
        else:
            b, resid_var = _finish_sharded_scaled(
                x_local, order, config, reducer, d
            )
        return order, b, resid_var

    fn = jax.shard_map(
        full_fit,
        mesh=mesh,
        in_specs=P(part.sample_axes, None),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn), m_pad, d_pad


def fit_sharded(x, config: FitConfig) -> FitResult:
    """The mesh plan of ``api.fit_fn``: pad, shard, run the full fit.

    Called by :func:`repro.core.api.fit_fn` when ``config.partition`` is
    set; returns the same :class:`FitResult` pytree as the local plan
    (bit-identical at the test-pinned parity cells; ulp-level agreement
    in general).
    """
    if config.partition is None:
        raise ValueError("fit_sharded requires config.partition")
    x = jnp.asarray(x, jnp.float32)
    m, d = x.shape
    fn, m_pad, d_pad = _build_sharded_fit(m, d, config)
    x_pad = jnp.pad(x, ((0, m_pad - m), (0, d_pad - d)))
    # Keyed on the *unpadded* (m, d) + config, matching the
    # compile_log.record("sharded.fit", ...) inside the trace body.
    order, b, resid_var = obs_profile.call(
        fn, x_pad, op="sharded.fit", shape=(m, d), config=config,
    )
    return FitResult(order=order, adjacency=b, resid_var=resid_var)


def make_sharded_causal_order(
    mesh,
    m: int,
    d: int,
    *,
    sample_axes=("data",),
    pair_axis="model",
    chunk: int = 512,
    backend: str = None,
    interpret: bool = None,
    fused_standardize: bool = False,
):
    """Build a jit-able sharded ordering fn for global data of shape (m, d).

    Ordering-only legacy entry point (the dry-run/roofline machinery
    lowers it); :func:`fit_sharded` is the full-fit product path. Returns
    (fn, m_pad, d_pad): call ``fn(x_padded)`` with x of shape
    (m_pad, d_pad) sharded P(sample_axes, None); returns the causal order
    (d,) replicated.

    ``fused_standardize`` (§Perf C2): fold standardization into the
    raw-X matmul, saving one standardized-slab pass per ordering step
    (see :meth:`MeshReducer.standardize`).
    """
    n_sample_shards = 1
    for ax in sample_axes:
        n_sample_shards *= mesh.shape[ax]
    axis_sizes = {ax: mesh.shape[ax] for ax in (*sample_axes, pair_axis)}

    m_pad = _round_up(m, n_sample_shards * chunk)
    d_pad = _round_up(d, mesh.shape[pair_axis])
    m_local = m_pad // n_sample_shards

    def ordered(x_local):
        reducer = MeshReducer(
            m=m, m_local=m_local, axis_sizes=axis_sizes,
            sample_axes=sample_axes, pair_axis=pair_axis, chunk=chunk,
            backend=backend, interpret=interpret,
            fused_standardize=fused_standardize,
        )
        return ordering.masked_order_impl(x_local, reducer, d=d)

    fn = jax.shard_map(
        ordered,
        mesh=mesh,
        in_specs=P(sample_axes, None),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn), m_pad, d_pad


def sharded_causal_order(x, mesh, **kw):
    """Convenience wrapper: pads, shards, runs, returns (d,) order."""
    m, d = x.shape
    fn, m_pad, d_pad = make_sharded_causal_order(mesh, m, d, **kw)
    x_pad = jnp.pad(jnp.asarray(x, jnp.float32), ((0, m_pad - m), (0, d_pad - d)))
    order = fn(x_pad)
    return order[:d] if d_pad != d else order
