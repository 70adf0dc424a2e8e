"""VarLiNGAM (Hyvarinen et al., 2010) — autoregressive LiNGAM extension.

    x(t) = sum_{tau=0..k} theta_tau x(t - tau) + e(t)

Procedure (paper §3.2):
  1. Fit a VAR(k) model by least squares -> coefficient matrices M_tau.
  2. Run DirectLiNGAM on the VAR residuals -> instantaneous matrix B0
     (this is where ~96% of the runtime goes, hence the same kernel).
  3. Transform the lagged coefficients: theta_tau = (I - B0) @ M_tau.

The VAR estimation is a single batched lstsq on TPU (the paper uses
statsmodels on CPU for this step); steps 1 and 3 each run as one jitted
program (:func:`estimate_var`, :func:`lag_transform`). Step 2 routes
through the functional core (``api.fit_fn``) — the facade only
orchestrates the VAR regression and the coefficient transform around the
pure fit. Setting ``partition``
runs that residual ordering on the mesh plan (``shard_map`` over the
configured device mesh) — with ``Partition(gather_finish=False)`` the
whole fit stays sharded end to end, which is how VarLiNGAM scales past
one device's memory on wide panels (the Jiao et al. scaling regime).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import api


@functools.partial(jax.jit, static_argnames=("lags",))
def estimate_var(x, lags: int = 1):
    """Least-squares VAR(k): returns (coefs [k, d, d], intercept [d],
    residuals [m - k, d]). One program, under ``lingam.var_regress``."""
    with jax.named_scope("lingam.var_regress"):
        x = jnp.asarray(x, dtype=jnp.float32)
        m, d = x.shape
        y = x[lags:]  # (m - k, d)
        z = jnp.concatenate(
            [x[lags - tau - 1 : m - tau - 1] for tau in range(lags)], axis=1
        )  # (m - k, k * d), column block tau holds x(t - tau - 1)
        z1 = jnp.concatenate([jnp.ones((y.shape[0], 1), x.dtype), z], axis=1)
        coef, *_ = jnp.linalg.lstsq(z1, y)
        intercept = coef[0]
        mats = coef[1:].T.reshape(d, lags, d).transpose(1, 0, 2)  # [k, d, d]
        resid = y - z1 @ coef
        return mats, intercept, resid


@jax.jit
def lag_transform(b0, mats):
    """theta_tau = (I - B0) M_tau for every lag: (k, d, d), one program."""
    with jax.named_scope("lingam.lag_transform"):
        eye = jnp.eye(b0.shape[0], dtype=b0.dtype)
        return jnp.stack([(eye - b0) @ mat for mat in mats])


@dataclasses.dataclass
class VarLiNGAM:
    lags: int = 1
    backend: Optional[str] = None
    interpret: Optional[bool] = None
    prune_method: str = "ols"
    prune_threshold: float = 0.0
    compaction: str = "none"
    partition: Optional[api.Partition] = None

    causal_order_: Optional[np.ndarray] = None
    adjacency_matrices_: Optional[List[np.ndarray]] = None  # [theta_0..k]
    var_coefs_: Optional[np.ndarray] = None
    residuals_: Optional[np.ndarray] = None
    result_: Optional[api.FitResult] = None

    def to_config(self) -> api.FitConfig:
        return api.FitConfig(
            backend=self.backend,
            interpret=self.interpret,
            prune_method=self.prune_method,
            prune_threshold=self.prune_threshold,
            compaction=self.compaction,
            partition=self.partition,
        )

    def fit(self, x) -> "VarLiNGAM":
        with obs.span("lingam.fit"):
            mats, _, resid = estimate_var(x, self.lags)
            result = api.fit_fn(resid, self.to_config())
            lagged = lag_transform(result.adjacency, mats)
            self.result_ = result
            with obs.span("lingam.fetch"):
                b0 = np.asarray(result.adjacency)
                self.adjacency_matrices_ = [b0, *np.asarray(lagged)]
                self.causal_order_ = np.asarray(result.order)
                self.var_coefs_ = np.asarray(mats)
                self.residuals_ = np.asarray(resid)
        return self


def fit_var_lingam(x, **kw) -> VarLiNGAM:
    return VarLiNGAM(**kw).fit(x)
