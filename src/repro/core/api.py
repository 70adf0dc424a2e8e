"""Functional estimator core: pure, jittable DirectLiNGAM fits.

The stateful ``DirectLiNGAM`` / ``VarLiNGAM`` dataclasses are facades over
the types here:

  * :class:`FitConfig` — frozen, hashable estimator settings. Passed as a
    *static* argument, so each distinct config compiles its own program.
  * :class:`Partition` — an optional mesh-partition spec inside the
    config: mesh axes/sizes, which axes shard the sample dimension, which
    axis tiles the (i, j) pair space, and the sample chunk size.
  * :class:`FitResult` — a registered pytree (order, adjacency,
    diagnostics) that flows freely through ``jit``/``vmap``/``scan``.

``fit_fn(x, config)`` is the whole fit — ordering + adjacency +
diagnostics — as one traced program with no host round-trips. The config
selects the execution plan; all three run the *same* ordering step
(:func:`repro.core.ordering.ordering_step`), differing only in how its
reductions execute:

  * **local** (``partition=None``) — plain ``jnp`` on one device.
  * **vmap** — the batched engine (:mod:`repro.core.batched`) maps the
    local plan over a leading dataset axis: ``vmap(fit_fn)`` over
    resamples or ensembles is a single compile.
  * **mesh** (``partition=Partition(...)``) — the fit compiles to a
    ``shard_map`` program (:mod:`repro.core.sharded`): samples sharded
    over the data axes (psum reductions), pair rows tiled over the model
    axis (all_gather), ordering with in-trace staged compaction, then
    row-sharded pruning — the d >> single-device-VMEM regime.

    from repro.core import api
    res = api.fit_fn(x, api.FitConfig(backend="pallas"))
    res.order       # (d,) int32 causal order
    res.adjacency   # (d, d) f32 connection strengths
    res.resid_var   # (d,) f32 residual noise variances

    mesh_cfg = api.FitConfig(
        compaction="staged",
        partition=api.Partition(mesh=(("data", 4), ("model", 2))),
    )
    res = api.fit_fn(x, mesh_cfg)   # same FitResult, 8 devices
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.obs import compile_log
from repro.obs import profile as obs_profile

from . import ordering, pruning


@dataclasses.dataclass(frozen=True)
class Partition:
    """Static mesh-partition spec for the mesh execution plan.

    ``mesh`` is a tuple of (axis_name, size) pairs, e.g.
    ``(("data", 4), ("model", 2))`` — the product must not exceed
    ``jax.device_count()``. ``sample_axes`` shard the sample dimension
    (psum-reduced); ``pair_axis`` tiles the (i, j) pair rows
    (all_gathered). ``chunk`` bounds the per-device sample chunk of the
    moment pass; samples are padded to a multiple of
    ``n_sample_shards * chunk`` and variables to a multiple of the pair
    axis size (padded columns enter inactive and are never selected).
    ``fused_standardize`` folds standardization into the raw-X matmul
    (§Perf C2: one standardized-slab pass saved per ordering step).

    ``gather_finish`` picks the adjacency/diagnostics tail:
      * ``True`` (default) — reassemble the dataset on each device and
        reduce the covariance in a fixed replicated order: bit-exact
        against the local plan (the parity tests pin this), but peak
        per-device memory is the full (m, d) slab.
      * ``False`` — fully sharded finish: covariance psum-reduced over
        sample shards, residual diagnostics on local rows. Per-device
        memory stays O(m_local * d + d^2) — the true d >> one-device
        regime — at ulp-level (reduction-order) agreement instead of
        bit-exactness.
    """

    mesh: Tuple[Tuple[str, int], ...] = (("data", 1), ("model", 1))
    sample_axes: Tuple[str, ...] = ("data",)
    pair_axis: str = "model"
    chunk: int = 512
    fused_standardize: bool = False
    gather_finish: bool = True

    def __post_init__(self):
        if isinstance(self.mesh, dict):
            object.__setattr__(self, "mesh", tuple(self.mesh.items()))
        else:
            object.__setattr__(
                self, "mesh", tuple((str(a), int(s)) for a, s in self.mesh)
            )
        if isinstance(self.sample_axes, str):
            object.__setattr__(self, "sample_axes", (self.sample_axes,))
        else:
            object.__setattr__(self, "sample_axes", tuple(self.sample_axes))
        names = [a for a, _ in self.mesh]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate axis names in mesh {self.mesh}")
        for ax in (*self.sample_axes, self.pair_axis):
            if ax not in names:
                raise ValueError(f"axis {ax!r} not in mesh {self.mesh}")
        if self.pair_axis in self.sample_axes:
            # An overlapping spec would psum different pair-row tiles
            # together (silently wrong moments), never just run slower.
            raise ValueError(
                f"pair_axis {self.pair_axis!r} must be disjoint from "
                f"sample_axes {self.sample_axes}"
            )


@dataclasses.dataclass(frozen=True)
class FitConfig:
    """Static (hashable) configuration of one DirectLiNGAM fit.

    ``prune_kwargs`` is stored as a sorted tuple of (key, value) pairs so
    the config stays hashable; passing a dict is fine — it is normalized
    on construction.

    ``compaction`` selects the ordering schedule:
      * ``"none"``   — the full masked scan (d identical steps; exact
                       legacy behaviour of ``ordering.causal_order``).
      * ``"staged"`` — in-trace active-set compaction
                       (``ordering.causal_order_compact``): same order,
                       ~2x fewer FLOPs, still a single compile. On a
                       mesh, stage widths stay multiples of the pair
                       axis size.

    ``partition`` selects the execution plan: ``None`` for the local
    (single-device / vmap) plan, a :class:`Partition` for the
    ``shard_map`` mesh plan.

    ``moment_chunk`` (local/vmap plans; ``blocked`` or ``pallas``
    backend) accumulates each ordering step's pairwise moments over
    (moment_chunk, d) sample slabs, bounding the per-step residual
    intermediate at O(chunk * d^2) — the streaming subsystem's
    rolling-window refits set this to the stream chunk size. The mesh
    plan chunks through ``Partition.chunk`` instead and ignores it.

    ``backend=None`` takes the platform's (pallas on accelerators,
    blocked elsewhere) and ``interpret=None`` resolves to
    interpret-only-when-no-accelerator. Kernel blocks follow from the
    shapes (:mod:`repro.kernels.tune.registry`).
    """

    backend: Optional[str] = None
    interpret: Optional[bool] = None
    prune_method: str = "ols"
    prune_threshold: float = 0.0
    prune_kwargs: Tuple[Tuple[str, Any], ...] = ()
    compaction: str = "none"
    compaction_frac: float = 0.25
    min_stage: int = 8
    partition: Optional[Partition] = None
    moment_chunk: Optional[int] = None

    def __post_init__(self):
        if isinstance(self.prune_kwargs, dict):
            object.__setattr__(
                self, "prune_kwargs", tuple(sorted(self.prune_kwargs.items()))
            )
        if self.moment_chunk is not None:
            if self.backend not in (None, "blocked", "pallas"):
                raise ValueError(
                    "moment_chunk requires the blocked or pallas backend "
                    f"(chunk accumulation has no {self.backend!r} variant)"
                )
            if self.moment_chunk < 1:
                raise ValueError(
                    f"moment_chunk must be >= 1, got {self.moment_chunk}"
                )

    @property
    def prune_kwargs_dict(self) -> Dict[str, Any]:
        return dict(self.prune_kwargs)


@dataclasses.dataclass
class FitResult:
    """One fit as a pytree. Under ``vmap`` every leaf gains the batch axis
    (``order``: (b, d), ``adjacency``: (b, d, d), ...)."""

    order: jax.Array       # (d,) int32 — position p holds the variable index
    adjacency: jax.Array   # (d, d) f32 — B[i, j] = effect of x_j on x_i
    resid_var: jax.Array   # (d,) f32 — Var(x_i - B_i x) diagnostic


jax.tree_util.register_dataclass(
    FitResult,
    data_fields=["order", "adjacency", "resid_var"],
    meta_fields=[],
)


def _order_for_config(x, config: FitConfig, valid=None):
    if valid is None:
        reducer = ordering.LocalReducer(
            backend=config.backend,
            interpret=config.interpret,
            moment_chunk=config.moment_chunk,
        )
    else:
        reducer = ordering.MaskedReducer(
            valid,
            backend=config.backend,
            interpret=config.interpret,
        )
    if config.compaction == "none":
        return ordering.masked_order_impl(x, reducer)
    if config.compaction == "staged":
        return ordering.compact_order_impl(
            x,
            reducer,
            frac=config.compaction_frac,
            min_stage=config.min_stage,
        )
    raise ValueError(f"unknown compaction: {config.compaction}")


def finish_fit(x, order, config: FitConfig, valid=None) -> FitResult:
    """Adjacency + residual diagnostics given the causal order.

    Shared tail of every plan: the mesh plan runs the sharded ordering
    and then this exact computation with its OLS row solves tiled over
    the pair axis via ``pruning.ols_rows`` — identical per-row
    arithmetic, so the plans' coefficients agree to the ulp-level
    lowering differences of batched solves (exactly, at the parity
    cells the tests pin). With ``valid`` (interventional data), each
    row's regression and residual variance run over the samples valid
    for its variable.
    """
    with jax.named_scope("lingam.prune"):
        b = pruning.estimate_adjacency(
            x,
            order,
            method=config.prune_method,
            threshold=config.prune_threshold,
            valid=valid,
            **config.prune_kwargs_dict,
        )
    with jax.named_scope("lingam.diagnostics"):
        xc = x - jnp.mean(x, axis=0, keepdims=True)
        resid = xc - xc @ b.T
        if valid is None:
            resid_var = jnp.mean(resid * resid, axis=0)
        else:
            n = jnp.maximum(jnp.sum(valid, axis=0), 1.0)
            resid = resid - jnp.sum(valid * resid, axis=0) / n
            resid_var = jnp.sum(valid * resid * resid, axis=0) / n
    return FitResult(order=order, adjacency=b, resid_var=resid_var)


def fit_impl(x, config: FitConfig, intervened=None) -> FitResult:
    """Unjitted trace body of the local plan (for callers composing
    larger programs — ``vmap`` in the batched engine, ...).

    ``intervened`` (optional, (m, d) bool) marks the samples in which an
    intervention set the variable; see :func:`fit_fn`.

    The stage spans here execute at *trace time* only (once per
    compile; tagged ``[trace]`` in the span tree) — they account for
    where trace construction goes, add nothing to the compiled
    program, and never run in steady state.
    """
    compile_log.record("core.fit", shape=x.shape, config=config)
    x = x.astype(jnp.float32)
    valid = None
    if intervened is not None:
        valid = jnp.logical_not(intervened).astype(jnp.float32)
    with obs.span("fit.ordering", d=x.shape[-1],
                  compaction=config.compaction):
        order = _order_for_config(x, config, valid)
    with obs.span("fit.pruning", method=config.prune_method):
        return finish_fit(x, order, config, valid)


@functools.partial(jax.jit, static_argnames=("config",))
def _fit_local(x, config: FitConfig, intervened=None) -> FitResult:
    return fit_impl(x, config, intervened)


def _check_intervened(x, intervened, config: FitConfig):
    """Reject what the interventional fit does not run: another shape
    than the data's, a mesh partition, moment chunking, or another
    pruner than OLS."""
    if tuple(intervened.shape) != tuple(x.shape):
        raise ValueError(
            f"intervened has shape {tuple(intervened.shape)}, the data "
            f"{tuple(x.shape)}; it needs one flag per sample and variable"
        )
    if config.partition is not None:
        raise ValueError(
            "the interventional fit runs the local plan only; the mesh "
            "plan takes no intervened mask — drop config.partition")
    if config.moment_chunk is not None:
        raise ValueError(
            "the interventional fit has no chunked moments; drop "
            "config.moment_chunk")
    if config.prune_method != "ols":
        raise ValueError(
            "the interventional fit prunes by OLS only, got "
            f"prune_method={config.prune_method!r}")


def fit_fn(x, config: FitConfig = FitConfig(), intervened=None) -> FitResult:
    """Pure DirectLiNGAM fit: (m, d) data + static config -> FitResult.

    The entire fit is one traced program (ordering scan, adjacency solve,
    diagnostics); no host transfers occur until the caller reads a leaf.
    With ``config.partition`` set, the program is a ``shard_map`` over
    the configured mesh (built from the process's devices) and returns
    the same ``FitResult`` pytree — bit-identical at the parity cells
    pinned by ``tests/test_mesh_fit.py``, and agreeing to fp32
    reduction order (ulps) in general.

    **Interventional data.** ``intervened`` is an (m, d) bool array,
    True where an intervention set that sample's value of that variable
    (a knockout screen: each cell's guide names the gene it knocked out).
    ``V = ~intervened`` is the validity mask, ``S_g`` the samples valid
    for variable ``g`` and ``S_ij = S_i & S_j``. At each ordering step, on
    the working data ``X``:

      1. ``mu_g``, ``sigma_g`` are the mean and (ddof=0) standard
         deviation over ``S_g``, ``z_g = (x_g - mu_g) / sigma_g``, and
         ``H(x_g)`` comes from ``E_{S_g}[log cosh z_g]`` and
         ``E_{S_g}[z_g exp(-z_g^2 / 2)]``.
      2. For each pair, over ``S_ij``: ``a_i`` and ``v_i`` are the mean
         and variance of ``z_i``, ``k_ij`` the covariance of ``z_i`` and
         ``z_j``, and ``u_{i<-j} = ((z_i - a_i) - (k_ij / v_j)(z_j - a_j))
         / sqrt(v_i - k_ij^2 / v_j)``; ``H(r_{i<-j})`` comes from the
         means of ``log cosh u`` and ``u exp(-u^2 / 2)`` over ``S_ij``.
      3. The scores are DirectLiNGAM's (``ordering.step_scores``).
      4. After root ``r`` is chosen, every remaining ``j`` takes
         ``x_j <- x_j - (Cov_{S_jr}(x_j, x_r) / Var_{S_jr}(x_r)) x_r`` on
         all samples.
      5. Pruning: ``B[j, P_j]`` is the OLS with intercept of ``x_j`` on its
         predecessors over ``S_j``; ``resid_var[j]`` is taken over ``S_j``.

    With no sample intervened this is the observational estimator. It is
    a different estimator, not a better one everywhere: ``S_ij`` also
    drops, from the pair ``(i, j)``, the samples in which the regressor
    ``j`` was knocked out, though they are draws of ``i``'s own equation
    and carry the knockout's effect on ``i``. On the ``gene-964-int``
    benchmark screens (4,096 cells x 964 genes, 192 genes knocked out in
    about 17 cells each; TPU v5e) the masked fit put 1.15-2.84% of the
    generator's true edges the wrong way round, against 1.02-1.30% for the
    pooled fit of the same cells, on each of six seeds. The interventional
    fit runs the local plan only (staged or full
    compaction, OLS pruning); a mesh partition, ``moment_chunk`` or
    another pruner raise ``ValueError``. ``None`` (the default) runs the
    observational program.
    """
    if intervened is not None:
        intervened = jnp.asarray(intervened, dtype=bool)
        _check_intervened(x, intervened, config)
    elif config.partition is not None:
        from . import sharded

        with obs.span("fit.mesh", m=x.shape[0], d=x.shape[1]):
            return sharded.fit_sharded(x, config)
    with obs.span("fit.local", m=x.shape[0], d=x.shape[1]):
        # Same (op, shape, config) signature as the compile_log.record
        # inside fit_impl, so cost rows join compile events.
        return obs_profile.call(
            _fit_local, x, config, intervened,
            op="core.fit", shape=x.shape, config=config,
        )


_STATS_EPS = 1e-12


def fit_impl_from_stats(x, mean, cov, config: FitConfig) -> FitResult:
    """Unjitted trace body of the from-stats fit (vmapped by
    ``batched.fit_many_from_stats``)."""
    compile_log.record("core.fit_from_stats", shape=x.shape, config=config)
    x = x.astype(jnp.float32)
    mean = mean.astype(jnp.float32)
    cov = cov.astype(jnp.float32)
    with jax.named_scope("lingam.standardize"):
        var = jnp.maximum(jnp.diagonal(cov), _STATS_EPS)
        x0 = (x - mean[None, :]) * jax.lax.rsqrt(var)[None, :]
    order = _order_for_config(x0, config)
    with jax.named_scope("lingam.prune"):
        b = pruning.estimate_adjacency_from_cov(
            cov,
            order,
            method=config.prune_method,
            threshold=config.prune_threshold,
            **config.prune_kwargs_dict,
        )
    with jax.named_scope("lingam.diagnostics"):
        r = jnp.eye(b.shape[0], dtype=b.dtype) - b
        resid_var = jnp.maximum(jnp.einsum("ij,jk,ik->i", r, cov, r), 0.0)
    return FitResult(order=order, adjacency=b, resid_var=resid_var)


@functools.partial(jax.jit, static_argnames=("config",))
def _fit_from_stats_local(x, mean, cov, config: FitConfig) -> FitResult:
    return fit_impl_from_stats(x, mean, cov, config)


def fit_from_stats(
    x, mean, cov, config: FitConfig = FitConfig(), intervened=None
) -> FitResult:
    """DirectLiNGAM fit that reuses precomputed sufficient statistics.

    The streaming entry point: ``mean``/``cov`` are the (d,) mean and
    (d, d) ddof=0 covariance of ``x`` — maintained incrementally by the
    rolling moment store (:mod:`repro.stream.stats`) rather than
    recomputed from the rows. They replace every data pass the fit can
    avoid:

      * the initial standardization uses the provided moments (the
        in-scan re-standardization then operates on already-clean
        columns — the ordering is affine-invariant per column);
      * adjacency pruning solves straight from ``cov``
        (:func:`repro.core.pruning.estimate_adjacency_from_cov`) — no
        O(m d^2) covariance matmul;
      * residual diagnostics come from ``diag((I-B) cov (I-B)^T)``,
        which equals the empirical residual variance exactly when
        ``cov`` is the sample covariance of ``x``.

    Only the nonlinear ordering moments still read the rows (they are
    standardization-dependent); ``config.moment_chunk`` bounds that pass
    at O(chunk) sample slabs. The mesh plan has no from-stats variant —
    partitioned configs are rejected with a pointer to ``fit_fn``.
    Neither has interventional data: ``intervened`` raises ``ValueError``
    (one covariance cannot stand for each variable's own samples).
    """
    if intervened is not None:
        raise ValueError(
            "fit_from_stats takes no intervened mask: each variable's "
            "moments would need its own samples; use fit_fn(x, config, "
            "intervened=...)")
    if config.partition is not None:
        raise ValueError(
            "fit_from_stats runs the local/vmap plans only; the mesh "
            "plan recomputes statistics shard-locally — drop "
            "config.partition or use fit_fn."
        )
    x = jnp.asarray(x)
    return obs_profile.call(
        _fit_from_stats_local, x, jnp.asarray(mean), jnp.asarray(cov), config,
        op="core.fit_from_stats", shape=x.shape, config=config,
    )
