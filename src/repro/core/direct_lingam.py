"""DirectLiNGAM (Shimizu et al., 2011) — the paper's accelerated target.

Public API:

    model = DirectLiNGAM(backend="pallas").fit(X)
    model.causal_order_   # (d,) — position p holds the variable index
    model.adjacency_      # (d, d) — B[i, j] = direct effect of x_j on x_i
    # knockout screen: M[s, g] True where sample s's guide knocked out g
    model = DirectLiNGAM(compaction="staged").fit(X, intervened=M)

The algorithm is unchanged from the sequential version (identical
identifiability guarantees, as the paper stresses); only the execution is
parallel. ``backend`` picks the pairwise-moment implementation:
"blocked" (vectorized jnp), "pallas" (TPU kernel; interpret=True on CPU),
or "ref" (small-problem oracle).

This class is a thin stateful facade over the functional core: ``fit``
builds a static :class:`~repro.core.api.FitConfig` and runs the pure
``api.fit_fn`` (one traced program), then materializes the result as
numpy attributes. Batched / bootstrap workloads should use
``repro.core.batched`` (``fit_many``) or
``repro.core.bootstrap.bootstrap_lingam`` directly, which vmap the same
``fit_fn`` instead of looping over facades.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro import obs

from . import api


@dataclasses.dataclass
class DirectLiNGAM:
    backend: Optional[str] = None
    interpret: Optional[bool] = None
    prune_method: str = "ols"
    prune_threshold: float = 0.0
    prune_kwargs: dict = dataclasses.field(default_factory=dict)
    compaction: str = "none"
    partition: Optional[api.Partition] = None

    causal_order_: Optional[np.ndarray] = None
    adjacency_: Optional[np.ndarray] = None
    resid_var_: Optional[np.ndarray] = None
    result_: Optional[api.FitResult] = None

    def to_config(self) -> api.FitConfig:
        """The static FitConfig equivalent of this facade's settings."""
        return api.FitConfig(
            backend=self.backend,
            interpret=self.interpret,
            prune_method=self.prune_method,
            prune_threshold=self.prune_threshold,
            prune_kwargs=dict(self.prune_kwargs),
            compaction=self.compaction,
            partition=self.partition,
        )

    def fit(self, x, intervened=None) -> "DirectLiNGAM":
        """Fit (m, d) data. ``intervened`` (optional, (m, d) bool) marks
        the samples in which an intervention set the variable (a
        knockout screen's targets); each variable's moments and
        regression then leave those samples out. That is a different
        estimator from the pooled fit, and on the benchmark's knockout
        screens it oriented fewer true edges than pooling; see
        ``api.fit_fn``."""
        with obs.span("lingam.fit"):
            x = jnp.asarray(x, dtype=jnp.float32)
            result = api.fit_fn(x, self.to_config(), intervened=intervened)
            self.result_ = result
            with obs.span("lingam.fetch"):
                self.causal_order_ = np.asarray(result.order)
                self.adjacency_ = np.asarray(result.adjacency)
                self.resid_var_ = np.asarray(result.resid_var)
        return self


def fit_direct_lingam(x, **kw) -> DirectLiNGAM:
    return DirectLiNGAM(**kw).fit(x)
