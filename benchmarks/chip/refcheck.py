"""The plain reference and the comparison that decides ``correct``.

Nothing here imports the program. The reference is DirectLiNGAM written
out once more, plainly:

* **Ordering.** At a step ``k`` of the program's order ``o``, the variables
  ``o[k:]`` are still to be ordered, and each has been regressed on
  ``o[:k]``. With ``cov_o = L L^T`` (the centered covariance in the order
  ``o``, float64 on the host) and ``Q = Xc_o L^-T``, those residuals are
  ``Q[:, k:] L[k:, k:]^T``. :func:`step_scores` scores every remaining
  variable from them (Hyvarinen's entropy approximation, the pairwise
  likelihood-ratio measure of Shimizu et al. 2011) in float32 at
  ``highest`` precision. The program's pick ``o[k]`` is judged by how far
  its score lies below the best, as a share of the spread of that step's
  scores (:func:`order_gaps`): a near-tie costs nothing, a wrong pick
  costs its distance. A fit reads the largest gap over the sampled steps
  (``order_gap``) and their mean (``order_gap_mean``), which one rare
  near-tie flip moves little and a pick that is wrong at every step moves
  fully.
* **Adjacency.** Regressing each variable on its predecessors is the
  Cholesky factorisation of ``cov_o``: ``B_o = I - diag(L) L^-1``
  (float64). The program's adjacency is compared by its largest entry
  error, as a share of the largest reference entry.
* **VAR(1).** Ordinary least squares with an intercept, in float64.
* **Lag transform.** ``theta_1 = (I - B0) M1`` from the program's own B0
  and M1, in float64 (:func:`lag_transform`), so that it checks the
  transform step alone.

The control (:func:`control_gaps`, :func:`bf16_cov` with
:func:`adjacency_from_cov_ldl`, :func:`control_var1_ols`) is the same
reference computed in bfloat16, the precision below the program's float32.
The program forms the lag transform with one matmul at the TPU's default
precision, whose products are bfloat16 already; its control
(:func:`control_lag_transform`) takes float8 (e4m3) factors, the step
below that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import workcount

HIGHEST = jax.lax.Precision.HIGHEST
K1 = 79.047
K2 = 7.4129
GAMMA = 0.37457
H_GAUSS = 0.5 * (1.0 + np.log(2.0 * np.pi))
LOG2 = float(np.log(2.0))
_NEG = -1e30


def _terms(u):
    au = jnp.abs(u)
    logcosh = au + jnp.log1p(jnp.exp(-2.0 * au)) - LOG2
    return logcosh, u * jnp.exp(-0.5 * u * u)


def _entropy(m1, m2):
    return H_GAUSS - K1 * (m1 - GAMMA) ** 2 - K2 * m2 ** 2


@functools.partial(jax.jit, static_argnames=("width", "dtype", "rows"))
def step_scores(q, l, k, *, width, dtype=jnp.float32, rows=8):
    """Scores of the variables ``o[k:]`` at ordering step ``k``.

    ``q`` is (m, d + width) and ``l`` (d + width, d + width), both
    zero-padded by ``width`` past ``d``; column ``j`` of the result is
    variable ``o[k + j]``. Columns past ``d - k`` are inactive (score
    ``-1e30``). ``width`` is a static bucket at least ``d - k``.
    """
    m = q.shape[0]
    d = q.shape[1] - width
    n_valid = d - k
    valid = jnp.arange(width) < n_valid
    qs = jax.lax.dynamic_slice(q, (0, k), (m, width))
    ls = jax.lax.dynamic_slice(l, (k, k), (width, width))
    ls = jnp.where(valid[:, None] & valid[None, :], ls, 0.0)
    r = jnp.dot(qs, ls.T, precision=HIGHEST).astype(dtype)

    mu = jnp.mean(r, axis=0)
    rc = r - mu
    var = jnp.mean(rc * rc, axis=0)
    u = jnp.where(valid, rc * jax.lax.rsqrt(jnp.maximum(var, 1e-30)), 0.0)
    u = u.astype(dtype)
    c = jnp.dot(u.T, u, precision=HIGHEST,
                preferred_element_type=dtype) / jnp.asarray(m, dtype)
    t1, t2 = _terms(u)
    h_col = _entropy(jnp.mean(t1, axis=0), jnp.mean(t2, axis=0))
    inv = jax.lax.rsqrt(jnp.maximum(1.0 - c * c, 1e-12)).astype(dtype)

    ut = u.T  # (width, m)
    n_blocks = width // rows

    def block(args):
        ui, ci, invi = args
        t = (ui[:, None, :] - ci[:, :, None] * ut[None]) * invi[:, :, None]
        a, b = _terms(t)
        return jnp.mean(a, axis=-1), jnp.mean(b, axis=-1)

    m1, m2 = jax.lax.map(block, (
        ut.reshape(n_blocks, rows, m),
        c.reshape(n_blocks, rows, width),
        inv.reshape(n_blocks, rows, width),
    ))
    h_res = _entropy(m1.reshape(width, width), m2.reshape(width, width))
    diff = (h_col[None, :] + h_res) - (h_col[:, None] + h_res.T)
    ok = valid[:, None] & valid[None, :] & ~jnp.eye(width, dtype=bool)
    score = -jnp.sum(jnp.where(ok, jnp.minimum(0.0, diff) ** 2, 0.0), axis=1)
    return jnp.where(valid, score.astype(jnp.float32), _NEG)


def bucket(n: int) -> int:
    """Static width for ``n`` live columns: few shapes, so few compiles."""
    return max(128, workcount.round_up(n, 128))


def whiten(x, order):
    """(Q, L) of the columns of ``x`` taken in ``order``, float64 on the
    host: ``cov_o = L L^T`` and ``Q = Xc_o L^-T``."""
    x = np.asarray(x, np.float64)[:, np.asarray(order)]
    xc = x - x.mean(axis=0)
    cov = xc.T @ xc / x.shape[0]
    l = np.linalg.cholesky(cov)
    q = np.linalg.solve(l, xc.T).T
    return q, l


def adjacency_from_cov(cov, order):
    """OLS of each variable on its predecessors in ``order``: B with
    ``B[i, j]`` the effect of j on i, via ``B_o = I - diag(L) L^-1``."""
    order = np.asarray(order)
    cov_o = np.asarray(cov, np.float64)[np.ix_(order, order)]
    l = np.linalg.cholesky(cov_o)
    b_o = np.eye(len(order)) - np.diag(np.diag(l)) @ np.linalg.inv(l)
    b = np.zeros_like(b_o)
    b[np.ix_(order, order)] = b_o
    return b


def adjacency_from_cov_ldl(cov, order):
    """The same regressions by an unpivoted ``LDL^T`` factorisation:
    ``B_o = I - L^-1`` with L unit lower-triangular. Unlike Cholesky it
    also factors a covariance that rounding has left indefinite, as the
    control's can be."""
    order = np.asarray(order)
    a = np.array(np.asarray(cov, np.float64)[np.ix_(order, order)])
    n = len(order)
    l = np.eye(n)
    for j in range(n):
        piv = a[j, j]
        if piv == 0.0 or not np.isfinite(piv):
            return np.full((n, n), np.inf)
        col = a[j + 1:, j] / piv
        l[j + 1:, j] = col
        a[j + 1:, j + 1:] -= np.outer(col, a[j, j + 1:])
    b_o = np.eye(n) - np.linalg.inv(l)
    b = np.zeros_like(b_o)
    b[np.ix_(order, order)] = b_o
    return b


def centered_cov(x):
    x = np.asarray(x, np.float64)
    xc = x - x.mean(axis=0)
    return xc.T @ xc / x.shape[0]


def sample_steps(d: int, rng, n_random: int):
    """Steps to check: each stage's first two steps, and ``n_random``
    more drawn from ``rng``; only steps with two or more candidates."""
    starts = []
    k = 0
    for _, n in workcount.stage_schedule(d):
        starts += [k, k + 1]
        k += n
    chosen = {s for s in starts if d - s >= 2}
    rest = np.array([s for s in range(d - 1) if s not in chosen])
    if len(rest) and n_random:
        take = rng.choice(rest, size=min(n_random, len(rest)), replace=False)
        chosen |= {int(s) for s in take}
    return sorted(chosen)


def _padded(q, l, width):
    d = l.shape[0]
    qp = np.zeros((q.shape[0], d + width), np.float32)
    qp[:, :d] = q
    lp = np.zeros((d + width, d + width), np.float32)
    lp[:d, :d] = l
    return jnp.asarray(qp), jnp.asarray(lp)


def _scores_by_step(q, l, steps, dtype):
    d = l.shape[0]
    out = {}
    padded = {}
    for k in steps:
        w = bucket(d - k)
        if w not in padded:
            padded[w] = _padded(q, l, w)
        qp, lp = padded[w]
        out[k] = step_scores(qp, lp, jnp.int32(k), width=w, dtype=dtype)
    return {k: np.asarray(v)[: d - k] for k, v in out.items()}


def _gap(scores, pick):
    best, worst = scores.max(), scores.min()
    spread = best - worst
    if not np.isfinite(spread) or spread <= 0.0:
        return 0.0 if scores[pick] == best else float("inf")
    return float((best - scores[pick]) / spread)


def order_gaps(q, l, steps):
    """Relative score gap of the program's pick (column 0) at each step."""
    ref = _scores_by_step(q, l, steps, jnp.float32)
    return {k: _gap(s, 0) for k, s in ref.items()}


def control_gaps(q, l, steps):
    """The control: at the same steps, the bfloat16 reference's pick,
    judged by the float32 reference's scores."""
    ref = _scores_by_step(q, l, steps, jnp.float32)
    low = _scores_by_step(q, l, steps, jnp.bfloat16)
    return {k: _gap(ref[k], int(np.argmax(low[k]))) for k in steps}


def order_gap_stats(x, order, rng, n_random: int, control: bool = False):
    """(largest, mean) of :func:`order_gaps` (with ``control``,
    :func:`control_gaps`) over the steps :func:`sample_steps` draws, for
    the columns of ``x`` ordered by the program as ``order``."""
    q, l = whiten(x, order)
    steps = sample_steps(l.shape[0], rng, n_random)
    gaps = list((control_gaps if control else order_gaps)(q, l, steps).values())
    return max(gaps), float(np.mean(gaps))


def is_permutation(order, d) -> bool:
    order = np.asarray(order)
    return order.shape == (d,) and np.array_equal(np.sort(order), np.arange(d))


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def bf16_cov(x):
    """The control's covariance: centered rows and their products in
    bfloat16."""
    xb = jnp.asarray(np.asarray(x, np.float32)).astype(jnp.bfloat16)
    xc = xb - jnp.mean(xb, axis=0)
    cov = jnp.dot(xc.T, xc, preferred_element_type=jnp.bfloat16)
    return np.asarray(cov.astype(jnp.float32), np.float64) / x.shape[0]


def var1_ols(rows):
    """VAR(1) with intercept by float64 least squares over consecutive
    ``rows``: (A, intercept, residuals) with ``x_t = c + A x_{t-1} + r_t``."""
    rows = np.asarray(rows, np.float64)
    y, z = rows[1:], rows[:-1]
    zc = z - z.mean(axis=0)
    yc = y - y.mean(axis=0)
    a = np.linalg.solve(zc.T @ zc, zc.T @ yc).T
    c = y.mean(axis=0) - a @ z.mean(axis=0)
    return a, c, yc - zc @ a.T


def control_var1_ols(rows):
    """The control's VAR(1) coefficients: the same regression from
    bfloat16 covariances."""
    rows = np.asarray(rows, np.float64)
    joint = bf16_cov(np.concatenate([rows[1:], rows[:-1]], axis=1))
    d = rows.shape[1]
    szz, syz = joint[d:, d:], joint[:d, d:]
    return np.linalg.solve(szz, syz.T).T


def lag_transform(b0, m1):
    """``theta_1 = (I - B0) M1`` in float64."""
    b0 = np.asarray(b0, np.float64)
    return (np.eye(b0.shape[0]) - b0) @ np.asarray(m1, np.float64)


def fp8(a):
    """``a`` rounded to float8 e4m3, scaled per tensor into its range."""
    import ml_dtypes

    a = np.asarray(a, np.float64)
    scale = 256.0 / max(float(np.abs(a).max()), 1e-30)
    low = (a * scale).astype(np.float32).astype(ml_dtypes.float8_e4m3fn)
    return low.astype(np.float64) / scale


def control_lag_transform(b0, m1):
    """The control's ``theta_1``: the product of float8 (e4m3) factors,
    accumulated in float64."""
    b0 = np.asarray(b0, np.float64)
    return fp8(np.eye(b0.shape[0]) - b0) @ fp8(m1)


def combine(numbers, limits):
    """[(name, value, limit)] and whether every value is within its limit."""
    table = [(n, float(numbers[n]), float(limits[n])) for n in limits]
    ok = all(np.isfinite(v) and v <= lim for _, v, lim in table)
    return table, ok
