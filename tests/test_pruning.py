"""``pruning.ols_from_cov``: every row's OLS from one Cholesky factor.

One parametrised test, each case a dataset and the reference its rows
are held to: float64 least squares on the predecessors, the per-row
masked LU solves the factor replaced, a duplicated column, and data with
no more rows than variables (the rank rule of the docstring).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pruning


def _lu_rows(cov, order):
    """Each row's own masked system, solved by LU: the per-row
    formulation the factor replaced."""
    mask = pruning.pred_mask(order)

    def solve_one(mask_i, cov_i):
        mm = mask_i[:, None] & mask_i[None, :]
        a = jnp.where(mm, cov, 0.0) + jnp.diag(
            jnp.where(mask_i, pruning.EPS, 1.0))
        return jnp.linalg.solve(a, jnp.where(mask_i, cov_i, 0.0))

    return jax.vmap(solve_one)(mask, cov)


def _lstsq_rows(x, order):
    """float64 least squares of each variable on its predecessors."""
    x = np.asarray(x, np.float64)
    x = x - x.mean(axis=0)
    b = np.zeros((x.shape[1],) * 2)
    for k in range(1, len(order)):
        pred = order[:k]
        b[order[k], pred] = np.linalg.lstsq(
            x[:, pred], x[:, order[k]], rcond=None)[0]
    return b


def _ridge_rows(x, order, share):
    """float64 rows of ``(S_k + EPS + share diag(S_k)) b = s_k``."""
    x = np.asarray(x, np.float64)
    x = x - x.mean(axis=0)
    cov = x.T @ x / x.shape[0]
    b = np.zeros_like(cov)
    for k in range(1, len(order)):
        p = order[:k]
        a = cov[np.ix_(p, p)]
        a = a + np.diag(pruning.EPS + share * np.diag(a))
        b[order[k], p] = np.linalg.solve(a, cov[p, order[k]])
    return b


def _data(m, d, seed):
    """A linear SEM over a random order, with unequal scales."""
    rng = np.random.default_rng(seed)
    e = rng.uniform(-1.0, 1.0, (m, d))
    w = np.tril(rng.uniform(-0.8, 0.8, (d, d)), -1)
    w *= rng.uniform(size=(d, d)) < min(1.0, 3.0 / d)
    x = e @ np.linalg.inv(np.eye(d) - w).T
    x = x * rng.uniform(0.5, 4.0, d)
    perm = rng.permutation(d)
    return x[:, perm].astype(np.float32), rng.permutation(d)


def _fit(x, order):
    x = jnp.asarray(x)
    xc = x - jnp.mean(x, axis=0)
    cov = xc.T @ xc / x.shape[0]
    b = pruning.ols_from_cov(cov, jnp.asarray(order), x.shape[0])
    return np.asarray(b), cov


def _rel(got, want):
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("case", [
    "lstsq-d9", "lstsq-d24", "lstsq-d130", "masked-lu",
    *[f"duplicated-column-{seed}" for seed in range(4)],
    "rows-below-d", "rows-equal-d",
])
def test_ols_from_cov(case):
    if case.startswith("lstsq"):
        d = int(case[len("lstsq-d"):])
        x, order = _data(4 * d + 100, d, seed=d)
        b, _ = _fit(x, order)
        assert _rel(b, _lstsq_rows(x, order)) < 2e-5
        assert np.all(b[pruning.pred_mask(jnp.asarray(order)) == 0] == 0)
    elif case == "masked-lu":
        x, order = _data(300, 24, seed=7)
        b, cov = _fit(x, order)
        assert _rel(b, np.asarray(_lu_rows(cov, jnp.asarray(order)))) < 2e-5
    elif case.startswith("duplicated-column"):
        # At m > d a duplicated column leaves the covariance singular:
        # the twins take the ridge, every other variable keeps EPS alone.
        # Without it the factor's pivot for the later twin is rounding of
        # either sign, NaN on most of these seeds.
        x, order = _data(500, 40, seed=int(case[-1]))
        x[:, 5] = x[:, 3]
        b, _ = _fit(x, order)
        assert np.isfinite(b).all()
        pos = np.argsort(order)
        first, second = sorted((3, 5), key=lambda v: pos[v])
        # The later twin is its earlier twin, less the ridge's shrinkage.
        np.testing.assert_allclose(b[second, first], 1.0, atol=1e-2)
        # Rows that see neither twin are plain OLS; the rest see the
        # ridge, and those after both twins split one coefficient.
        ref = _lstsq_rows(np.delete(x, second, axis=1),
                          [v - (v > second) for v in order if v != second])
        ref = np.insert(np.insert(ref, second, 0.0, axis=0), second, 0.0,
                        axis=1)
        neither = order[:pos[first] + 1]
        assert _rel(b[neither], ref[neither]) < 2e-5
        rest = order[pos[first] + 1:]
        rest = rest[rest != second]
        merged = b[rest].copy()
        merged[:, first] += merged[:, second]
        merged[:, second] = 0.0
        assert _rel(merged, ref[rest]) < 1e-2
    else:
        # n_rows <= d: rank below d, so every variable takes the ridge.
        d = 40
        m = 30 if case == "rows-below-d" else d
        x, order = _data(m, d, seed=11)
        b, _ = _fit(x, order)
        assert np.isfinite(b).all()
        want = _ridge_rows(x, order, pruning._RANK_RIDGE)
        assert _rel(b, want) < 1e-2
