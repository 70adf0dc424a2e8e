"""Batched execution engine: many DirectLiNGAM fits as one program.

The paper's accelerated ordering makes a *single* fit fast; its
applications (gene networks, stock graphs) need *many* fits — bootstrap
resamples, ensembles over datasets, scenario sweeps. This module turns
``api.fit_fn`` into a device-parallel engine:

  * :func:`fit_many` — ``vmap(fit_fn)`` over a leading dataset axis:
    (b, m, d) -> batched :class:`~repro.core.api.FitResult`. One compile
    for the whole ensemble.
  * :func:`resample_indices` — bootstrap index matrix generated on-device
    with ``jax.random`` (deterministic in the seed; shared by the vmap
    engine and the host-loop fallback so both fit identical resamples).
  * :func:`bootstrap_fits` — gather + vmapped refit of all resamples in a
    single jitted call: the resample gather, every ordering scan, every
    adjacency solve, and the edge statistics all live in one XLA program.

This module is the **vmap** execution plan of the shared ordering step
(:func:`repro.core.ordering.ordering_step`): it maps the local plan's
reducer over a leading dataset axis — the mesh plan
(``FitConfig.partition``) is the orthogonal scale-out direction and
cannot be nested inside ``vmap`` (both would claim the devices), so
partitioned configs are rejected here with a pointer to ``fit_fn``.

Under ``vmap`` the staged-compaction ordering (``compaction="staged"``)
still works: each batch element gathers along its *own* surviving
columns (batched ``take``), so the engine keeps compaction's ~2x FLOP
cut on top of batching.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.obs import compile_log
from repro.obs import profile as obs_profile

from .api import FitConfig, FitResult, fit_impl, fit_impl_from_stats


def pow2_bucket(n: int, cap: int) -> int:
    """Next power of two >= n, capped at ``cap`` — the shared
    micro-batch padding policy: serving fit batches, query-engine
    buckets, and RCA sample slabs all round partial batches up to a
    bounded set of program shapes (log2(cap) + 1 of them) instead of
    compiling one program per distinct length."""
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def _require_local_plan(config: FitConfig, engine: str) -> None:
    if config.partition is not None:
        raise ValueError(
            f"{engine} vmaps the local execution plan and cannot nest a "
            "mesh partition; drop config.partition, or fit each dataset "
            "through api.fit_fn (the mesh plan) / serve the batch via "
            "CausalDiscoveryEngine, which routes partitioned configs "
            "per-dataset."
        )


@functools.partial(jax.jit, static_argnames=("config",))
def _fit_many_jit(xs, config: FitConfig) -> FitResult:
    _require_local_plan(config, "fit_many")
    compile_log.record("batched.fit_many", shape=xs.shape, config=config)
    return jax.vmap(lambda x: fit_impl(x, config))(xs)


def fit_many(xs, config: FitConfig = FitConfig(), intervened=None
             ) -> FitResult:
    """Fit every dataset in ``xs`` (b, m, d); returns a batched FitResult
    (order: (b, d), adjacency: (b, d, d), resid_var: (b, d)). The engine
    runs observational fits only: ``intervened`` raises ``ValueError``."""
    if intervened is not None:
        raise ValueError(
            "fit_many takes no intervened mask: the vmap engine runs the "
            "observational fit only; fit each interventional dataset "
            "through api.fit_fn(x, config, intervened=...)")
    return obs_profile.call(
        _fit_many_jit, xs, config,
        op="batched.fit_many", shape=xs.shape, config=config,
    )


@functools.partial(jax.jit, static_argnames=("config",))
def _fit_many_from_stats_jit(xs, means, covs, config: FitConfig) -> FitResult:
    _require_local_plan(config, "fit_many_from_stats")
    compile_log.record(
        "batched.fit_many_from_stats", shape=xs.shape, config=config
    )
    return jax.vmap(
        lambda x, mu, cv: fit_impl_from_stats(x, mu, cv, config)
    )(xs, means, covs)


def fit_many_from_stats(
    xs, means, covs, config: FitConfig = FitConfig()
) -> FitResult:
    """Batched :func:`~repro.core.api.fit_from_stats`: datasets (b, m, d)
    with their precomputed moments — means (b, d), ddof=0 covariances
    (b, d, d) — fit as one vmapped program. The serving engine routes
    due stream-session refits here so a burst of rolling windows costs
    one device-parallel dispatch instead of b sequential fits."""
    return obs_profile.call(
        _fit_many_from_stats_jit, xs, means, covs, config,
        op="batched.fit_many_from_stats", shape=xs.shape, config=config,
    )


def warmup_fit_many(shape, config: FitConfig = FitConfig(), *, batch: int = 1):
    """Prime the vmap plan for datasets of ``shape`` before traffic
    arrives: one zeros-fit traces + compiles ``fit_many``. The serving
    engine's ``warmup`` calls this so first requests pay no compile."""
    m, d = shape
    xs = jnp.zeros((batch, m, d), jnp.float32)
    jax.block_until_ready(fit_many(xs, config).order)


@functools.partial(jax.jit, static_argnames=("n_sampling", "m"))
def resample_indices(seed, n_sampling: int, m: int):
    """(n_sampling, m) int32 bootstrap row indices, drawn on-device."""
    key = jax.random.key(seed)
    return jax.random.randint(key, (n_sampling, m), 0, m, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("config",))
def _bootstrap_fits_jit(x, indices, config: FitConfig) -> FitResult:
    _require_local_plan(config, "bootstrap_fits")
    compile_log.record(
        "batched.bootstrap_fits", shape=indices.shape, config=config
    )
    xs = jnp.take(x.astype(jnp.float32), indices, axis=0)  # (b, m, d)
    return jax.vmap(lambda xb: fit_impl(xb, config))(xs)


def bootstrap_fits(x, indices, config: FitConfig = FitConfig()) -> FitResult:
    """All bootstrap refits as one compiled program.

    Args:
      x:       (m, d) data.
      indices: (n_sampling, m) int32 resample rows (see
               :func:`resample_indices`).
    Returns:
      The batched FitResult over resamples (adjacency: (n_sampling, d, d)).
      Edge statistics are a cheap host-side reduction over it
      (``bootstrap._summarize``), kept out of this program so threshold
      sweeps reuse the compile cache.
    """
    return obs_profile.call(
        _bootstrap_fits_jit, x, indices, config,
        op="batched.bootstrap_fits", shape=indices.shape, config=config,
    )


@functools.partial(jax.jit, static_argnames=("config", "post"))
def _bootstrap_fits_with_jit(
    x, indices, config: FitConfig, post
) -> "tuple[FitResult, object]":
    _require_local_plan(config, "bootstrap_fits_with")
    xs = jnp.take(x.astype(jnp.float32), indices, axis=0)  # (b, m, d)

    def one(xb):
        r = fit_impl(xb, config)
        return r, post(r)

    return jax.vmap(one)(xs)


def bootstrap_fits_with(
    x, indices, config: FitConfig, post
) -> "tuple[FitResult, object]":
    """:func:`bootstrap_fits` plus a per-resample in-trace reduction.

    ``post`` (static — pass a module-level function, not a lambda, or
    every call re-traces) maps each resample's :class:`FitResult` to an
    arbitrary pytree *inside* the same compiled program, so derived
    statistics — the query subsystem's total-effect matrices, for one
    (:func:`repro.infer.effects.bootstrap_effects`) — cost no extra
    dispatch or host round-trip. Returns ``(batched FitResult, batched
    post pytree)``.
    """
    return obs_profile.call(
        _bootstrap_fits_with_jit, x, indices, config, post,
        op="batched.bootstrap_fits_with", shape=indices.shape, config=config,
    )
