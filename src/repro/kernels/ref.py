"""Pure-jnp oracle for the pairwise-statistics kernel.

Given standardized data ``X_std`` of shape (m, d) and its sample
correlation matrix ``C`` (d, d), computes for every ordered pair (i, j):

    r_ij    = x_i - C[i, j] * x_j                 (regression residual)
    u_ij    = r_ij / std(r_ij) = r_ij / sqrt(1 - C[i, j]^2)
    M1[i,j] = E[log cosh u_ij]
    M2[i,j] = E[u_ij * exp(-u_ij^2 / 2)]

The identity std(r_ij) = sqrt(1 - C_ij^2) holds *exactly* in sample moments
when X is standardized with ddof=0 and C is the ddof=0 sample correlation.

This is the oracle the Pallas kernel is validated against; it materializes
the full (d, d, m) residual tensor, so only use it for small problems.
``pairwise_moments_blocked`` in ops.py is the memory-bounded jnp fallback.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .nonlinearity import nonlinear_terms

EPS = 1e-12


def standardize(x, axis=0, eps=EPS):
    """Zero-mean / unit-std (ddof=0) along ``axis``."""
    mu = jnp.mean(x, axis=axis, keepdims=True)
    sd = jnp.std(x, axis=axis, keepdims=True)
    return (x - mu) / jnp.maximum(sd, eps)


def correlation(x_std):
    """Sample correlation of standardized data, (m, d) -> (d, d)."""
    m = x_std.shape[0]
    return (x_std.T @ x_std) / m


def pairwise_moments_ref(x_std, c):
    """Oracle: full-materialization pairwise residual moments.

    Args:
      x_std: (m, d) standardized samples.
      c:     (d, d) sample correlation.
    Returns:
      (M1, M2): each (d, d), fp32. Diagonal entries are the moments of the
      degenerate self-residual (r_ii = x_i - x_i = 0 scaled by rsqrt(eps));
      callers mask the diagonal.
    """
    xt = x_std.T.astype(jnp.float32)  # (d, m)
    c = c.astype(jnp.float32)
    r = xt[:, None, :] - c[:, :, None] * xt[None, :, :]  # (d, d, m)
    inv_std = jax.lax.rsqrt(jnp.maximum(1.0 - c * c, EPS))
    u = r * inv_std[:, :, None]
    logcosh, uexp = nonlinear_terms(u)
    m1 = jnp.mean(logcosh, axis=-1)
    m2 = jnp.mean(uexp, axis=-1)
    return m1, m2


def pairwise_moments_masked_ref(z, valid):
    """Oracle of the masked moments, from their definition.

    Args:
      z:     (m, d) standardized samples.
      valid: (m, d) 1.0 where the sample counts for the variable, else 0.
    Returns:
      (M1, M2): each (d, d), fp32. For each pair, over ``S_ij``, the
      samples valid for both: ``a_i``, ``v_i`` the mean and variance of
      ``z_i``, ``k_ij`` the covariance, ``c = k_ij / v_j`` and
      ``u = ((z_i - a_i) - c (z_j - a_j)) / sqrt(v_i - k_ij c)``;
      ``M1 = E_{S_ij}[log cosh u]``, ``M2 = E_{S_ij}[u exp(-u^2 / 2)]``.
      Materializes (d, d, m): small problems only.
    """
    zt = z.T.astype(jnp.float32)                     # (d, m)
    vt = valid.T.astype(jnp.float32)
    w = vt[:, None, :] * vt[None, :, :]              # (d, d, m)
    n = jnp.maximum(jnp.sum(w, axis=-1), 1.0)

    def mean(t):
        return jnp.sum(w * t, axis=-1) / n

    a_i = mean(zt[:, None, :])                       # [i, j]: E_{S_ij} z_i
    a_j = mean(zt[None, :, :])
    d_i = zt[:, None, :] - a_i[:, :, None]
    d_j = zt[None, :, :] - a_j[:, :, None]
    v_i = mean(d_i * d_i)
    v_j = jnp.maximum(mean(d_j * d_j), EPS)
    k = mean(d_i * d_j)
    beta = k / v_j
    r = d_i - beta[:, :, None] * d_j
    u = r * jax.lax.rsqrt(jnp.maximum(v_i - k * beta, EPS))[:, :, None]
    logcosh, uexp = nonlinear_terms(u)
    return mean(logcosh), mean(uexp)
