"""The plain reference of the interventional fit and the comparison that
decides ``correct`` in its cell.

Nothing here imports the program; :mod:`refcheck` lends its helpers.
``valid[s, g]`` is True where no knockout set gene ``g`` in row ``s``;
``S_g`` is the set of such rows and ``S_ij = S_i & S_j``. The estimator,
written out once more:

* **Ordering.** At step ``k`` of the program's order ``o``, the variables
  ``o[k:]`` are left, and each has been regressed, on all rows, on the
  roots ``o[:k]`` one after another, each coefficient
  ``Cov(x_j, x_r) / Var(x_r)`` taken over ``S_jr``. The coefficients are
  pair-specific, so :mod:`refcheck`'s Cholesky shortcut does not hold:
  :func:`replay` repeats the residualisation in float64 on the host.
  :func:`masked_step_scores` scores the variables left from those
  residuals in float32, in blocks of rows: each
  standardised over its own ``S_g``; each pair's residual regressed and
  standardised over ``S_ij``, two-pass, on the pair's own rows. The
  program's pick is judged by :func:`refcheck._gap`.
* **Adjacency.** Each variable regressed, with an intercept, on its
  predecessors over its ``S_j`` (:func:`masked_ols`, float64).

The control is the same reference in bfloat16: its picks at the program's
steps (judged by the float32 scores) and its regressions from bfloat16
covariances.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import refcheck


def _wmean(w, t, n):
    return jnp.sum(w * t, axis=-1) / n


@functools.partial(jax.jit, static_argnames=("width", "dtype", "rows"))
def masked_step_scores(r, v, n_valid, *, width, dtype=jnp.float32, rows=8):
    """Scores of the ``n_valid`` variables in the columns of ``r``.

    ``r`` (m, width) holds their residuals (float32), ``v`` (m, width)
    their validity (1.0 or 0.0); columns past ``n_valid`` are inactive
    (score ``-1e30``). ``width`` is a static bucket.
    """
    live = jnp.arange(width) < n_valid
    v = jnp.where(live[None, :], v, 0.0).astype(dtype)
    r = r.astype(dtype)
    cnt = jnp.maximum(jnp.sum(v, axis=0), 1.0)
    mu = jnp.sum(v * r, axis=0) / cnt
    rc = r - mu
    var = jnp.sum(v * rc * rc, axis=0) / cnt
    z = jnp.where(live, rc * jax.lax.rsqrt(jnp.maximum(var, 1e-30)), 0.0)
    z = z.astype(dtype)
    t1, t2 = refcheck._terms(z)
    h_col = refcheck._entropy(jnp.sum(v * t1, axis=0) / cnt,
                              jnp.sum(v * t2, axis=0) / cnt)

    zt, vt = z.T, v.T                                  # (width, m)

    def block(args):
        zi, vi = args                                   # (rows, m)
        w = vi[:, None, :] * vt[None]                   # (rows, width, m)
        n = jnp.maximum(jnp.sum(w, axis=-1), 1.0)
        di = zi[:, None, :] - _wmean(w, zi[:, None, :], n)[:, :, None]
        dj = zt[None] - _wmean(w, zt[None], n)[:, :, None]
        vi_ = _wmean(w, di * di, n)
        vj_ = jnp.maximum(_wmean(w, dj * dj, n), 1e-12)
        k = _wmean(w, di * dj, n)
        c = k / vj_
        res = (di - c[:, :, None] * dj) * jax.lax.rsqrt(
            jnp.maximum(vi_ - k * c, 1e-12))[:, :, None]
        a, b = refcheck._terms(res.astype(dtype))
        return _wmean(w, a, n), _wmean(w, b, n)

    n_blocks = width // rows
    m1, m2 = jax.lax.map(block, (zt.reshape(n_blocks, rows, -1),
                                 vt.reshape(n_blocks, rows, -1)))
    h_res = refcheck._entropy(m1.reshape(width, width),
                              m2.reshape(width, width))
    diff = (h_col[None, :] + h_res) - (h_col[:, None] + h_res.T)
    ok = live[:, None] & live[None, :] & ~jnp.eye(width, dtype=bool)
    score = -jnp.sum(jnp.where(ok, jnp.minimum(0.0, diff) ** 2, 0.0), axis=1)
    return jnp.where(live, score.astype(jnp.float32), refcheck._NEG)


def replay(x, valid, order, steps):
    """``{k: (residuals, validity)}`` at each step ``k`` in ``steps``: the
    columns ``o[k:]`` of ``x`` after the program's prefix ``o[:k]`` has
    been regressed out of them, in float64.

    Columns are kept variables-major in order. The regressions of one
    step read every remaining column once (two sums per column, less the
    few rows where the column or the root is invalid); the updates are
    applied in blocks of ``block`` steps as one matrix product.
    """
    order = np.asarray(order)
    xs = np.ascontiguousarray(np.asarray(x, np.float64)[:, order].T)
    vs = np.asarray(valid, bool)[:, order].T           # (d, m)
    d, m = xs.shape
    want = set(int(k) for k in steps)
    out = {}
    inv_g, inv_s = np.nonzero(~vs)                     # invalid entries
    block = 32
    for k0 in range(0, d, block):
        k1 = min(k0 + block, d)
        roots = np.zeros((k1 - k0, m))                 # current root rows
        coefs = np.zeros((k1 - k0, d))                 # pending updates
        for k in range(k0, k1):
            p = k - k0
            if k in want:
                cur = xs[k:] - coefs[:p, k:].T @ roots[:p]
                out[k] = (cur.T, vs[k:].T)
            xr = xs[k] - coefs[:p, k] @ roots[:p]
            roots[p] = xr
            if k == d - 1:
                break
            a = vs[k].astype(np.float64)
            y = np.stack([a, a * xr])                  # (2, m)
            # sums over S_jr of x_j (column 0) and x_j x_r (column 1) for
            # every remaining j: all rows where r is valid, less the rows
            # where j is not
            sums = xs[k + 1:] @ y.T - coefs[:p, k + 1:].T @ (roots[:p] @ y.T)
            sel = inv_g > k
            g, s = inv_g[sel], inv_s[sel]
            xgs = xs[g, s]
            if p:
                xgs = xgs - np.einsum("pe,pe->e", coefs[:p, g], roots[:p, s])
            for col, wts in enumerate((a[s], a[s] * xr[s])):
                sums[:, col] -= np.bincount(
                    g - (k + 1), weights=wts * xgs, minlength=d - k - 1)
            rest = np.stack([a, a * xr, a * xr * xr]).sum(axis=1)
            moments = np.tile(rest, (d - k - 1, 1))    # sums of 1, x_r, x_r^2
            for col, wts in enumerate((a[s], a[s] * xr[s],
                                       a[s] * xr[s] ** 2)):
                moments[:, col] -= np.bincount(
                    g - (k + 1), weights=wts, minlength=d - k - 1)
            n = np.maximum(moments[:, 0], 1.0)
            mean_r = moments[:, 1] / n
            mean_j = sums[:, 0] / n
            cov = sums[:, 1] / n - mean_j * mean_r
            var_r = np.maximum(moments[:, 2] / n - mean_r * mean_r, 1e-300)
            coefs[p, k + 1:] = cov / var_r
        xs[k1:] -= coefs[:, k1:].T @ roots
    return out


def _scores_by_step(states, dtype):
    out = {}
    for k, (r, v) in states.items():
        w = refcheck.bucket(r.shape[1])
        rp = np.zeros((r.shape[0], w), np.float32)
        vp = np.zeros((r.shape[0], w), np.float32)
        rp[:, : r.shape[1]] = r
        vp[:, : r.shape[1]] = v
        out[k] = masked_step_scores(jnp.asarray(rp), jnp.asarray(vp),
                                    jnp.int32(r.shape[1]), width=w,
                                    dtype=dtype)
    return {k: np.asarray(s)[: states[k][0].shape[1]] for k, s in out.items()}


def order_gap_stats(x, valid, order, rng, n_random: int,
                    control: bool = False):
    """(largest, mean) gap of the program's picks (with ``control``, of
    the bfloat16 reference's) at the steps :func:`refcheck.sample_steps`
    draws."""
    steps = refcheck.sample_steps(len(order), rng, n_random)
    states = replay(x, valid, order, steps)
    ref = _scores_by_step(states, jnp.float32)
    if control:
        low = _scores_by_step(states, jnp.bfloat16)
        gaps = [refcheck._gap(ref[k], int(np.argmax(low[k]))) for k in steps]
    else:
        gaps = [refcheck._gap(ref[k], 0) for k in steps]
    return max(gaps), float(np.mean(gaps))


def _regress_rows(b, cov_of, rows, order):
    """Overwrite each row ``j`` of ``b`` with the regression of ``j`` on
    its predecessors from ``cov_of(j)``."""
    pos = np.empty(len(order), int)
    pos[np.asarray(order)] = np.arange(len(order))
    for j in rows:
        preds = np.asarray(order)[: pos[j]]
        b[j] = 0.0
        if len(preds):
            c = cov_of(j)
            b[j, preds] = np.linalg.solve(c[np.ix_(preds, preds)],
                                          c[preds, j])
    return b


def masked_ols(x, valid, order):
    """``B[j, P_j]``: OLS with intercept of ``x_j`` on its predecessors
    over ``S_j``, float64. Rows valid everywhere share the covariance of
    all rows (one Cholesky, :func:`refcheck.adjacency_from_cov`); each
    other row downdates it by its invalid rows."""
    x = np.asarray(x, np.float64)
    valid = np.asarray(valid, bool)
    xc = x - x.mean(axis=0)
    gram = xc.T @ xc
    total = xc.sum(axis=0)
    b = refcheck.adjacency_from_cov(gram / x.shape[0], order)

    def cov_of(j):
        out = xc[~valid[:, j]]
        n = x.shape[0] - len(out)
        mean = (total - out.sum(axis=0)) / n
        return (gram - out.T @ out) / n - np.outer(mean, mean)

    rows = np.nonzero(~valid.all(axis=0))[0]
    return _regress_rows(b, cov_of, rows, order)


def control_masked_ols(x, valid, order):
    """The control's regressions: each covariance from bfloat16 products
    (:func:`refcheck.bf16_cov` over the row's valid rows), factored
    without pivoting for the shared one as :mod:`refcheck`'s control is;
    the rows with their own covariance solved by LU."""
    x = np.asarray(x, np.float64)
    valid = np.asarray(valid, bool)
    b = refcheck.adjacency_from_cov_ldl(refcheck.bf16_cov(x), order)
    rows = np.nonzero(~valid.all(axis=0))[0]
    return _regress_rows(
        b, lambda j: refcheck.bf16_cov(x[valid[:, j]]), rows, order)


def rows_err(got, want, rows) -> float:
    """:func:`refcheck.rel_err` over the adjacency rows ``rows``."""
    if not len(rows):
        return 0.0
    return refcheck.rel_err(np.asarray(got)[rows], np.asarray(want)[rows])


def misoriented_share(order, b_true) -> float:
    """Share of the true edges ``j -> i`` that ``order`` puts ``i``
    before ``j``."""
    pos = np.empty(len(order), int)
    pos[np.asarray(order)] = np.arange(len(order))
    child, parent = np.nonzero(np.asarray(b_true))
    if not len(child):
        return 0.0
    return float(np.mean(pos[parent] > pos[child]))
