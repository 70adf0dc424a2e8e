"""Adjacency estimation given a causal order.

After DirectLiNGAM establishes the order k(.), the connection strengths are
estimated by regressing each variable on its predecessors. The paper leaves
this on CPU (numpy/sklearn, ~4% of runtime); here every variable's OLS
comes from one Cholesky factor of the covariance taken in causal order
(:func:`ols_from_cov`), plus an optional adaptive-lasso refinement (FISTA
on the weighted-L1 problem, the jax-native equivalent of lingam's
LassoLarsIC step). Interventional data, where each variable has its own
samples, solves one masked system per variable
(:func:`masked_ols_adjacency`).

The lasso's per-variable solves are row-independent given the
(replicated) correlation, so the fully sharded mesh finish
(:mod:`repro.core.sharded`) calls :func:`lasso_rows` on its pair-axis
tile and ``all_gather``s the rows; the OLS factor is milliseconds and
runs replicated on every device.
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

#: Share of its variance added to the diagonal of each variable the
#: factor in :func:`ols_from_cov` cannot resolve in fp32 (see there).
_RANK_RIDGE = 2.0 ** -10

#: Two columns with ``corr^2 >= 1 - _TWIN_GAP`` count as collinear: the
#: later one's pivot, at most ``_TWIN_GAP`` of its variance, is then
#: within reach of the factor's fp32 rounding.
_TWIN_GAP = 2.0 ** -16


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


def ols_from_cov(cov, order, n_rows: int):
    """OLS of every variable on its causal predecessors, from the (ddof=0)
    covariance of ``n_rows`` samples.

    With ``S`` the covariance taken in causal order (``cov[order][:,
    order]``, ``EPS`` on its diagonal as every row's system has) and
    ``S = C C^T`` its Cholesky factor, S's leading k x k block is the
    system of the k-th variable in the order and C's leading block
    factors it, so one factor holds every row's regression:
    ``B_ord = I - diag(C) C^-1``, strictly lower triangular, scattered
    back out of the order. The triangular solve runs its products at
    ``HIGHEST`` precision.

    **Rank.** Where S is singular the fp32 factor breaks down (a pivot
    that rounding leaves negative is NaN) and OLS has no unique answer.
    The rule: every variable gets ``_RANK_RIDGE`` (2^-10) of its
    variance on S's diagonal when ``n_rows <= d`` (the covariance has
    rank below d); otherwise only a variable whose column is collinear
    with another's (``corr^2 >= 1 - _TWIN_GAP``, a duplicated column)
    gets it. Rows that see such a variable come back as the finite ridge
    estimates; every other variable's diagonal carries ``EPS`` alone.
    Collinearity among three or more columns at ``n_rows > d`` is not
    detected and can still give NaN rows.

    The data-free tail of :func:`ols_adjacency`: given the centered
    covariance — from raw data, or merged incrementally by the streaming
    moment store — no further data pass is needed.
    """
    d = cov.shape[0]
    s = cov[order][:, order]
    var = jnp.diagonal(s)
    corr2 = s * s / jnp.maximum(var[:, None] * var[None, :], 1e-30)
    twin = jnp.any(
        (corr2 >= 1.0 - _TWIN_GAP) & ~jnp.eye(d, dtype=bool), axis=1)
    ridge = jnp.where(twin | (n_rows <= d), _RANK_RIDGE * var, 0.0)
    c = jnp.linalg.cholesky(s + jnp.diag(EPS + ridge))
    with jax.default_matmul_precision("highest"):
        c_inv = jax.scipy.linalg.solve_triangular(
            c, jnp.eye(d, dtype=s.dtype), lower=True)
    b_ord = jnp.tril(-jnp.diagonal(c)[:, None] * c_inv, -1)
    pos = jnp.argsort(order)
    return b_ord[pos][:, pos]


@functools.partial(jax.jit, static_argnames=())
def ols_adjacency(x, order):
    """OLS adjacency: B[i, j] = coefficient of x_j in the regression of
    x_i on its causal predecessors (:func:`ols_from_cov` of the data's
    covariance).
    """
    m, d = x.shape
    xc = x - jnp.mean(x, axis=0, keepdims=True)
    cov = (xc.T @ xc) / m  # (d, d)
    return ols_from_cov(cov, order, m)


@jax.jit
def masked_ols_adjacency(x, order, valid):
    """OLS with intercept of each variable on its causal predecessors,
    over the samples valid for it (interventional data).

    ``valid[s, j]`` is 1.0 where sample ``s`` counts for variable ``j``
    (no intervention set ``x_j`` there), else 0.0. Row ``j`` solves the
    masked system of its own covariance over ``S_j``, ``xc^T diag(V_j) xc
    / n_j - mu_j mu_j^T`` (``xc`` centered once over all samples, which
    moves no covariance and keeps the one-pass form well conditioned), at
    full fp32 matmul precision. Rows are solved in memory-bounded
    batches (:func:`_map_rows`).
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


@functools.partial(jax.jit, static_argnames=("n_rows", "n_steps"))
def adaptive_lasso_from_cov(cov, order, n_rows, lam=0.01, gamma=1.0,
                            n_steps=400):
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
    b_ols = ols_from_cov(cov, order, n_rows) * (sd[None, :] / sd[:, None])
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
    cov, order, n_rows: int, method: str = "ols", threshold: float = 0.0,
    **kw
):
    """:func:`estimate_adjacency` from precomputed moments (no data pass).

    Every supported pruner reads the data only through its centered
    covariance, so a caller holding sufficient statistics (the streaming
    moment store, ``api.fit_from_stats``) skips the O(m d^2) covariance
    matmul entirely.
    """
    if method == "ols":
        b = ols_from_cov(cov, order, n_rows)
    elif method == "adaptive_lasso":
        b = adaptive_lasso_from_cov(cov, order, n_rows, **kw)
    else:
        raise ValueError(f"unknown method: {method}")
    return apply_threshold(b, threshold)
