"""Adjacency estimation given a causal order.

After DirectLiNGAM establishes the order k(.), the connection strengths are
estimated by regressing each variable on its predecessors. The paper leaves
this on CPU (numpy/sklearn, ~4% of runtime); here it is vectorized as a
masked *batched* OLS (one vmapped linear solve per variable) plus an
optional adaptive-lasso refinement (FISTA on the weighted-L1 problem, the
jax-native equivalent of lingam's LassoLarsIC step).

The per-variable solves are row-independent given the (replicated)
covariance, so the mesh execution plan (:mod:`repro.core.sharded`) calls
the row-tile entry points (:func:`ols_rows`, :func:`lasso_rows`) on its
pair-axis tile and ``all_gather``s the rows — bit-identical to the
single-device solve because each row's computation is unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EPS = 1e-9

#: Floats one batch of row solves may hold per copy of its systems. Each
#: row builds its own masked (d, d) system, so solving all d rows at
#: once holds d^3 floats per copy (about 13 GiB of temporaries at
#: d = 964); rows are solved in batches of at most this many floats
#: instead — one batch, i.e. a plain vmap, whenever d^3 fits.
_SOLVE_BATCH_FLOATS = 1 << 24


def _map_rows(fn, *rows):
    """``vmap(fn)`` over the leading row axis, in memory-bounded batches."""
    d = rows[0].shape[-1]
    batch = max(1, _SOLVE_BATCH_FLOATS // (d * d))
    return jax.lax.map(lambda r: fn(*r), rows, batch_size=batch)


def pred_mask(order):
    """(d, d) bool: mask[i, j] = True iff j precedes i in the causal order."""
    d = order.shape[0]
    pos = jnp.zeros((d,), jnp.int32).at[order].set(jnp.arange(d, dtype=jnp.int32))
    return pos[None, :] < pos[:, None]


_pred_mask = pred_mask  # backwards-compatible private alias


def ols_rows(cov, mask_rows, cov_rows):
    """Masked OLS solves for a tile of variables.

    Args:
      cov:       (d, d) covariance of the centered data (replicated).
      mask_rows: (tile, d) predecessor masks for the tile's variables.
      cov_rows:  (tile, d) the same variables' covariance rows.
    Returns:
      (tile, d) coefficient rows. Rows whose mask is all-False (e.g.
      mesh padding rows) solve an identity system and come back zero.
    """

    def solve_one(mask_i, cov_xi):
        mm = mask_i[:, None] & mask_i[None, :]
        a = jnp.where(mm, cov, 0.0) + jnp.diag(jnp.where(mask_i, EPS, 1.0))
        b = jnp.where(mask_i, cov_xi, 0.0)
        return jnp.linalg.solve(a, b)

    return _map_rows(solve_one, mask_rows, cov_rows)


def ols_from_cov(cov, order):
    """Masked OLS adjacency from a precomputed (ddof=0) covariance.

    The data-free tail of :func:`ols_adjacency`: given the centered
    covariance — from raw data, or merged incrementally by the streaming
    moment store — the per-variable solves need no further data pass.
    """
    mask = pred_mask(order)  # (d, d)
    return ols_rows(cov, mask, cov)


@functools.partial(jax.jit, static_argnames=())
def ols_adjacency(x, order):
    """Batched masked OLS: B[i, j] = coefficient of x_j in the regression of
    x_i on its causal predecessors. Rows/cols outside the predecessor set are
    pinned via an identity-augmented system so one vmapped solve handles all
    variables with static shapes.
    """
    m, d = x.shape
    xc = x - jnp.mean(x, axis=0, keepdims=True)
    cov = (xc.T @ xc) / m  # (d, d)
    return ols_from_cov(cov, order)


@jax.jit
def masked_ols_adjacency(x, order, valid):
    """OLS with intercept of each variable on its causal predecessors,
    over the samples valid for it (interventional data).

    ``valid[s, j]`` is 1.0 where sample ``s`` counts for variable ``j``
    (no intervention set ``x_j`` there), else 0.0. Row ``j`` solves the
    masked system of its own covariance over ``S_j``, ``xc^T diag(V_j) xc
    / n_j - mu_j mu_j^T`` (``xc`` centered once over all samples, which
    moves no covariance and keeps the one-pass form well conditioned), at
    full fp32 matmul precision. Rows are solved in the same
    memory-bounded batches as :func:`ols_rows`.
    """
    x = x.astype(jnp.float32)
    xc = x - jnp.mean(x, axis=0, keepdims=True)
    hi = jax.lax.Precision.HIGHEST
    n = jnp.maximum(jnp.sum(valid, axis=0), 1.0)          # (d,)
    means = jnp.dot(valid.T, xc, precision=hi) / n[:, None]  # row j: S_j

    def solve_one(j, v_j, mean_j, n_j, mask_j):
        cov = (jnp.dot((xc * v_j[:, None]).T, xc, precision=hi) / n_j
               - jnp.outer(mean_j, mean_j))
        mm = mask_j[:, None] & mask_j[None, :]
        a = jnp.where(mm, cov, 0.0) + jnp.diag(jnp.where(mask_j, EPS, 1.0))
        return jnp.linalg.solve(a, jnp.where(mask_j, cov[j], 0.0))

    d = x.shape[1]
    return _map_rows(solve_one, jnp.arange(d), valid.T, means, n,
                     pred_mask(order))


def _soft_threshold(z, t):
    return jnp.sign(z) * jnp.maximum(jnp.abs(z) - t, 0.0)


def lasso_rows(cov, mask_rows, cov_rows, w_rows, lam, lip, n_steps):
    """FISTA adaptive-lasso solves for a tile of variables.

    Args:
      cov:       (d, d) correlation of the standardized data (replicated).
      mask_rows: (tile, d) predecessor masks.
      cov_rows:  (tile, d) correlation rows of the tile's variables.
      w_rows:    (tile, d) adaptive weights 1/|b_ols|^gamma.
    Returns:
      (tile, d) standardized-unit coefficient rows.
    """
    d = cov.shape[0]

    def fista(mask_i, cov_xi, w_i):
        mm = mask_i[:, None] & mask_i[None, :]
        a = jnp.where(mm, cov, 0.0)
        g = jnp.where(mask_i, cov_xi, 0.0)

        def step(carry, _):
            b, y, t = carry
            grad = a @ y - g
            b_new = _soft_threshold(y - grad / lip, lam * w_i / lip)
            b_new = jnp.where(mask_i, b_new, 0.0)
            t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
            y_new = b_new + ((t - 1.0) / t_new) * (b_new - b)
            return (b_new, y_new, t_new), None

        b0 = jnp.zeros((d,), jnp.float32)
        (b, _, _), _ = jax.lax.scan(
            step, (b0, b0, jnp.float32(1.0)), None, length=n_steps
        )
        return b

    return _map_rows(fista, mask_rows, cov_rows, w_rows)


@functools.partial(jax.jit, static_argnames=("n_steps",))
def adaptive_lasso_adjacency(x, order, lam=0.01, gamma=1.0, n_steps=400):
    """Adaptive lasso via FISTA, weights w_j = 1/|b_ols_j|^gamma.

    Solved in *standardized* units (correlation matrix) so ``lam`` is
    dimensionless and the quadratic is well conditioned (L <= d); the
    coefficients are rescaled back to raw units at the end. Per variable i
    (vectorized over i):
        min_b 0.5 b^T R b - r_i^T b + lam * sum_j w_j |b_j|
    Predecessors enter through masks so shapes stay static.
    """
    m, d = x.shape
    sd = jnp.maximum(jnp.std(x, axis=0), 1e-12)
    xc = (x - jnp.mean(x, axis=0, keepdims=True)) / sd
    cov = (xc.T @ xc) / m  # correlation
    mask = pred_mask(order)  # (d, d) bool
    # OLS weights in standardized units.
    b_ols_raw = ols_adjacency(x, order)
    b_ols = b_ols_raw * (sd[None, :] / sd[:, None])
    w = 1.0 / jnp.maximum(jnp.abs(b_ols), 1e-3) ** gamma  # (d, d)

    # Lipschitz bound: trace of the correlation matrix = d (cheap, safe).
    lip = jnp.float32(d)

    b_std = lasso_rows(cov, mask, cov, w, lam, lip, n_steps)
    return b_std * (sd[:, None] / sd[None, :])


@functools.partial(jax.jit, static_argnames=("n_steps",))
def adaptive_lasso_from_cov(cov, order, lam=0.01, gamma=1.0, n_steps=400):
    """Adaptive lasso from a precomputed (ddof=0) covariance.

    Same estimator as :func:`adaptive_lasso_adjacency` with the
    correlation and OLS weights derived from ``cov`` instead of a data
    pass (the standardized-unit quadratic is identical in exact
    arithmetic; fp32 agreement is to reduction order). This is the
    streaming path: the rolling moment store hands its merged covariance
    straight to the solver.
    """
    d = cov.shape[0]
    sd = jnp.maximum(jnp.sqrt(jnp.maximum(jnp.diagonal(cov), 0.0)), 1e-12)
    corr = cov / (sd[:, None] * sd[None, :])
    mask = pred_mask(order)
    b_ols = ols_from_cov(cov, order) * (sd[None, :] / sd[:, None])
    w = 1.0 / jnp.maximum(jnp.abs(b_ols), 1e-3) ** gamma
    lip = jnp.float32(d)
    b_std = lasso_rows(corr, mask, corr, w, lam, lip, n_steps)
    return b_std * (sd[:, None] / sd[None, :])


def apply_threshold(b, threshold: float):
    """Zero entries with |B_ij| < threshold (no-op for threshold <= 0)."""
    if threshold > 0.0:
        b = jnp.where(jnp.abs(b) >= threshold, b, 0.0)
    return b


def estimate_adjacency(
    x, order, method: str = "ols", threshold: float = 0.0, valid=None, **kw
):
    """Adjacency matrix B with B[i, j] = direct effect of x_j on x_i.

    With ``valid`` (interventional data, ``ols`` only), each row is
    fitted over the samples valid for its variable
    (:func:`masked_ols_adjacency`)."""
    if valid is not None:
        if method != "ols":
            raise ValueError(
                f"interventional pruning is ols only, got {method!r}")
        b = masked_ols_adjacency(x, order, valid)
    elif method == "ols":
        b = ols_adjacency(x, order)
    elif method == "adaptive_lasso":
        b = adaptive_lasso_adjacency(x, order, **kw)
    else:
        raise ValueError(f"unknown method: {method}")
    return apply_threshold(b, threshold)


def estimate_adjacency_from_cov(
    cov, order, method: str = "ols", threshold: float = 0.0, **kw
):
    """:func:`estimate_adjacency` from precomputed moments (no data pass).

    Every supported pruner reads the data only through its centered
    covariance, so a caller holding sufficient statistics (the streaming
    moment store, ``api.fit_from_stats``) skips the O(m d^2) covariance
    matmul entirely.
    """
    if method == "ols":
        b = ols_from_cov(cov, order)
    elif method == "adaptive_lasso":
        b = adaptive_lasso_from_cov(cov, order, **kw)
    else:
        raise ValueError(f"unknown method: {method}")
    return apply_threshold(b, threshold)
