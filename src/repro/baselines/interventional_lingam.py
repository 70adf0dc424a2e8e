"""Sequential (numpy, pair-loop) DirectLiNGAM on interventional data — the
plain reference for ``api.fit_fn(x, config, intervened=M)``.

Written from the estimator's equations as :mod:`sequential_lingam` is for
the pooled fit: python loops over (i, j) pairs, each pair's statistics
taken on its own samples. ``valid[s, g]`` is True where no intervention
set variable ``g`` in sample ``s``; ``S_g`` is the set of such samples and
``S_ij = S_i & S_j``. At each ordering step, on the working data:

1. ``z_g`` is ``x_g`` standardized by its mean and (ddof=0) standard
   deviation over ``S_g``; ``H(x_g)`` is the entropy of ``z_g`` over
   ``S_g``.
2. For each pair, ``r_{i<-j}`` is the residual of regressing ``z_i`` on
   ``z_j`` (with intercept) over ``S_ij``, standardized over ``S_ij``;
   ``H(r_{i<-j})`` is its entropy there.
3. The score of ``i`` is ``-sum_j min(0, (H(x_j) + H(r_{i<-j})) -
   (H(x_i) + H(r_{j<-i})))^2``; the root is the highest.
4. Every remaining ``j`` takes ``x_j - (Cov(x_j, x_r) / Var(x_r)) x_r``
   on all samples, the moments taken over ``S_jr``.

Pruning regresses each variable on its predecessors, with an intercept,
over ``S_j``. With every sample valid this is :mod:`sequential_lingam`.
Float64 throughout.
"""

from __future__ import annotations

import numpy as np

from .sequential_lingam import _entropy


def _standardize(v: np.ndarray) -> np.ndarray:
    return (v - v.mean()) / max(v.std(), 1e-12)


def _residual(xi: np.ndarray, xj: np.ndarray) -> np.ndarray:
    """Residual of regressing ``xi`` on ``xj`` with intercept (ddof=0)."""
    ci, cj = xi - xi.mean(), xj - xj.mean()
    return ci - (np.mean(ci * cj) / max(np.mean(cj * cj), 1e-12)) * cj


def search_causal_order(x, valid, u_idx) -> int:
    """The most exogenous variable among ``u_idx``."""
    z = {}
    h = {}
    for g in u_idx:
        s = valid[:, g]
        mu, sd = x[s, g].mean(), max(x[s, g].std(), 1e-12)
        z[g] = (x[:, g] - mu) / sd
        h[g] = _entropy(z[g][s])
    k_list = []
    for i in u_idx:
        k = 0.0
        for j in u_idx:
            if i == j:
                continue
            s = valid[:, i] & valid[:, j]
            zi, zj = z[i][s], z[j][s]
            h_ri = _entropy(_standardize(_residual(zi, zj)))
            h_rj = _entropy(_standardize(_residual(zj, zi)))
            k += min(0.0, (h[j] + h_ri) - (h[i] + h_rj)) ** 2
        k_list.append(-k)
    return int(u_idx[int(np.argmax(k_list))])


def causal_order(x, intervened) -> np.ndarray:
    """The causal order of ``x`` (m, d); ``intervened`` (m, d) bool."""
    x = np.array(x, dtype=np.float64, copy=True)
    valid = ~np.asarray(intervened, bool)
    u_idx = list(range(x.shape[1]))
    order = []
    while u_idx:
        root = search_causal_order(x, valid, u_idx)
        for j in u_idx:
            if j == root:
                continue
            s = valid[:, j] & valid[:, root]
            cj = x[s, j] - x[s, j].mean()
            cr = x[s, root] - x[s, root].mean()
            coef = np.mean(cj * cr) / max(np.mean(cr * cr), 1e-12)
            x[:, j] = x[:, j] - coef * x[:, root]
        u_idx.remove(root)
        order.append(root)
    return np.array(order, dtype=np.int64)


def ols_adjacency(x, intervened, order) -> np.ndarray:
    """``B[j, P_j]``: OLS with intercept of ``x_j`` on its predecessors
    ``P_j`` over ``S_j``."""
    x = np.asarray(x, dtype=np.float64)
    valid = ~np.asarray(intervened, bool)
    b = np.zeros((x.shape[1], x.shape[1]))
    for p, j in enumerate(order):
        preds = np.asarray(order[:p])
        if len(preds) == 0:
            continue
        s = valid[:, j]
        zp = x[s][:, preds] - x[s][:, preds].mean(axis=0)
        yj = x[s, j] - x[s, j].mean()
        b[j, preds] = np.linalg.lstsq(zp, yj, rcond=None)[0]
    return b


def fit(x, intervened):
    """(order, adjacency) of the interventional DirectLiNGAM fit."""
    order = causal_order(x, intervened)
    return order, ols_adjacency(x, intervened, order)
