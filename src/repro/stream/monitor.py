"""Graph-health monitoring: streaming drift detection on served graphs.

A rolling refit silently overwrites the served graph; the scenario that
makes streaming causal discovery valuable at scale (markets,
microservices, gene panels) is *detecting when the causal mechanism
itself changes*. This module watches exactly that signal: the
structural noise of the currently-served graph,

    ``e = (I - B0) r``,  ``r = y - c - A z``

with ``[y, z]`` a chunk's lag-augmented rows and ``(B0, A, c,
resid_var)`` the served VarLiNGAM estimate. Under the served model the
per-variable noises are zero-mean, variance ``resid_var``, and mutually
independent — three testable invariants, each broken by a different
kind of structural change:

  * **mean shift** of ``e_j`` — intercept / regression-weight drift
    moving residual means (CUSUM on the standardized chunk mean; alert
    kind ``"weight-shift"``);
  * **variance shift** of ``e_j`` — the noise mechanism re-scaled, or
    un-modeled weight change leaking into the residual (CUSUM on the
    likelihood-ratio-style standardized variance statistic; alert kind
    ``"noise-scale"``);
  * **cross-dependence** between ``e_j`` and the other noises — edges
    appeared/flipped that the served ``B0`` no longer removes (CUSUM on
    an LM-type score from the chunk's residual correlations; alert
    kind ``"edge-flip"``).

Everything is computed **purely from the chunk's**
:class:`~repro.stream.stats.MomentState` — the (count, mean, centered
M2) summary the rolling window already produces per slide — so
monitoring costs one small jitted transform per chunk and never
re-reads rows (``tests/test_monitor.py`` pins zero extra data passes).
The transform is one compiled program per ``(d, lags)`` shape shared
across every session, with a vmapped batch entry
(:func:`score_chunks_many`) whose micro-batches pad to the size
:func:`_batch_bucket` gives.

Alerts are :class:`DriftAlert` objects carrying the implicated
variable, the firing statistic, a kind label, and candidate root
variables ranked via :func:`repro.infer.rca.drift_root_candidates`
(drift scores live in the structural-noise frame — the same frame RCA
decomposes into — so propagation to descendants is already
deconvolved). :class:`repro.stream.session.StreamSession` consumes
them for adaptive refit cadence (refit early on alert, coast while
stable) and :class:`repro.serve.engine.CausalDiscoveryEngine` surfaces
them through ``poll_alerts`` / flush deltas and ``obs.metrics``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics

from . import stats as stats_lib
from . import window as window_lib

_EPS = 1e-12

# Statistic index -> the structural-change kind it evidences.
STAT_KINDS = ("weight-shift", "noise-scale", "edge-flip")


@dataclasses.dataclass(frozen=True)
class MonitorConfig:
    """Sequential-test knobs of one graph-health monitor.

    The per-chunk statistics are standardized to ~unit scale under the
    served model, then accumulated in per-variable CUSUMs:
    ``S <- max(0, S + z - slack)``, alerting when ``S > threshold``.
    ``slack`` absorbs steady model error (the served graph is itself an
    estimate); ``threshold`` sets the false-alarm / detection-delay
    trade-off (~``threshold / (|z| - slack)`` chunks to detect a shift
    of size ``z``). ``var_slack`` adds slack to the variance statistic,
    whose null spread is widest under heavy-tailed (LiNGAM) noise —
    fourth moments are not in the moment state, so it cannot be
    kurtosis-corrected exactly.
    """

    slack: float = 1.0
    threshold: float = 14.0
    var_slack: float = 1.0      # extra slack for the variance statistic
    dep_slack: float = 0.5      # extra slack for the dependence statistic
    min_count: int = 8          # skip chunks with fewer effective rows
    max_pending: int = 64       # bounded per-session pending-alert ring
    history: int = 256          # bounded per-session alert history ring
    rca_top_k: int = 3          # candidate roots attached per alert
    cooldown: int = 2           # chunks between repeat alerts of one
    #                             (variable, kind) while the same drift
    #                             episode keeps accumulating


@dataclasses.dataclass
class DriftAlert:
    """One sequential test crossing its alarm level."""

    sid: str                 # owning stream session ("" for library use)
    variable: int            # variable whose invariant broke
    kind: str                # "weight-shift" | "noise-scale" | "edge-flip"
    score: float             # CUSUM level / threshold (>= 1.0 at fire)
    stat: float              # the chunk statistic that tipped it
    chunk_index: int         # session chunk count when it fired
    refit_index: int         # refits completed when it fired
    candidate_roots: List[Tuple[int, float]]  # [(variable, drift score)]
    #                          ranked via infer.rca.drift_root_candidates

    def summary(self) -> str:
        roots = ", ".join(f"x{v}:{s:.1f}" for v, s in self.candidate_roots)
        return (
            f"drift[{self.kind}] x{self.variable} score={self.score:.2f} "
            f"stat={self.stat:+.2f} chunk={self.chunk_index} "
            f"roots=[{roots}]"
        )


@jax.jit
def chunk_drift_stats(count, mean, m2, a, intercept, b0, resid_var):
    """Per-variable standardized drift statistics of one chunk, from
    its augmented :class:`MomentState` leaves alone.

    Args:
      count/mean/m2: the chunk's augmented moment summary — mean is
        ``((k+1)d,)``, m2 the centered ``((k+1)d, (k+1)d)`` sums.
      a:         (d, k d) served VAR coefficients.
      intercept: (d,) served VAR intercept.
      b0:        (d, d) served instantaneous adjacency.
      resid_var: (d,) served structural-noise variances.

    Returns ``(z_mean, z_var, z_dep)``, each ``(d,)``:
      * ``z_mean`` — chunk mean of ``e_j`` over its served standard
        error ``sqrt(resid_var_j / n)`` (~N(0,1) under the model);
      * ``z_var``  — ``(vhat_j / resid_var_j - 1) * sqrt(n / 2)``, the
        standardized Gaussian likelihood-ratio direction for a variance
        change (``vhat`` is the chunk's second moment of ``e_j`` about
        the model's zero mean, so un-modeled mean shifts surface here
        too);
      * ``z_dep``  — LM-type dependence score: mean over partners of
        ``n * corr(e_j, e_i)^2`` (each ~chi^2(1) under independence),
        centered and scaled to ~unit variance.

    The noise moments come from the linear maps ``r = y - c - A z``,
    ``e = (I - B0) r`` applied to the chunk's mean/covariance — exact,
    no row access.
    """
    from repro.obs import compile_log

    compile_log.record("monitor.chunk_drift_stats", shape=b0.shape)
    d = b0.shape[0]
    n = jnp.maximum(count, 1.0)
    cov_u = m2 / n
    mean_r = mean[:d] - a @ mean[d:] - intercept
    czy = cov_u[d:, :d]
    cov_r = (
        cov_u[:d, :d] - a @ czy - czy.T @ a.T + a @ cov_u[d:, d:] @ a.T
    )
    r0 = jnp.eye(d, dtype=b0.dtype) - b0
    mean_e = r0 @ mean_r
    cov_e = r0 @ cov_r @ r0.T
    v0 = jnp.maximum(resid_var, _EPS)

    z_mean = mean_e * jnp.sqrt(n / v0)
    vhat = jnp.maximum(jnp.diagonal(cov_e), 0.0) + mean_e**2
    z_var = (vhat / v0 - 1.0) * jnp.sqrt(n / 2.0)

    sd = jnp.sqrt(jnp.maximum(jnp.diagonal(cov_e), _EPS))
    corr = cov_e / (sd[:, None] * sd[None, :])
    corr = corr - jnp.diag(jnp.diagonal(corr))
    n_partners = jnp.maximum(d - 1, 1)
    dep = n * jnp.sum(corr**2, axis=1) / n_partners
    z_dep = (dep - 1.0) * jnp.sqrt(n_partners / 2.0)
    return z_mean, z_var, z_dep


_chunk_drift_stats_many = jax.jit(
    jax.vmap(chunk_drift_stats, in_axes=(0, 0, 0, 0, 0, 0, 0))
)


@dataclasses.dataclass
class ServedGraph:
    """The monitor's frozen view of the estimate it scores against."""

    a: np.ndarray          # (d, k d) VAR coefficients
    intercept: np.ndarray  # (d,)
    b0: np.ndarray         # (d, d) instantaneous adjacency
    order: np.ndarray      # (d,) causal order (for RCA ranking)
    resid_var: np.ndarray  # (d,)

    @classmethod
    def from_fit(cls, fit: window_lib.RollingFit) -> "ServedGraph":
        mats = np.asarray(fit.var_coefs)
        a = np.concatenate(list(mats), axis=1)  # [k, d, d] -> (d, k d)
        if fit.intercept is None:
            raise ValueError(
                "RollingFit.intercept missing — refit through "
                "finish_refit to monitor this graph"
            )
        return cls(
            a=a.astype(np.float32),
            intercept=np.asarray(fit.intercept, np.float32),
            b0=np.asarray(fit.result.adjacency, np.float32),
            order=np.asarray(fit.result.order),
            resid_var=np.asarray(fit.result.resid_var, np.float32),
        )


class GraphHealthMonitor:
    """Per-session sequential tests on a served graph's noise residuals.

    Lifecycle: :meth:`arm` freezes the served estimate and zeroes the
    CUSUM banks; :meth:`update` scores one chunk's
    :class:`MomentState` and returns any :class:`DriftAlert`\\ s that
    fired. ``max_score`` summarizes the current drift level (max CUSUM
    over variables and statistics, normalized by the threshold — 1.0
    means "at the alarm level"), which the session stamps into its
    :class:`~repro.stream.session.GraphDelta`.
    """

    def __init__(self, config: MonitorConfig, d: int, lags: int,
                 sid: str = ""):
        self.config = config
        self.d = d
        self.lags = lags
        self.sid = sid
        self.graph: Optional[ServedGraph] = None
        self.n_scored = 0
        # CUSUM banks, (3, d): mean/var two-sided kept as (pos, neg).
        self._pos = np.zeros((3, d), np.float32)
        self._neg = np.zeros((3, d), np.float32)
        self._last_alert: Dict[Tuple[int, str], int] = {}

    @property
    def armed(self) -> bool:
        return self.graph is not None

    def arm(self, fit: window_lib.RollingFit) -> None:
        """Adopt a freshly served estimate; restart the tests."""
        self.graph = ServedGraph.from_fit(fit)
        self._pos[:] = 0.0
        self._neg[:] = 0.0
        self._last_alert.clear()

    def max_score(self) -> float:
        """Current drift level: max CUSUM / threshold (1.0 = alarm)."""
        if self.graph is None:
            return 0.0
        peak = max(float(self._pos.max()), float(self._neg.max()))
        return peak / self.config.threshold

    def variable_scores(self) -> np.ndarray:
        """(d,) per-variable drift level (max over statistics / sides,
        normalized by the threshold) — the structural-noise-frame score
        vector RCA ranks root candidates from."""
        return (
            np.maximum(self._pos, self._neg).max(axis=0)
            / self.config.threshold
        )

    def _slacks(self) -> np.ndarray:
        c = self.config
        return np.array(
            [c.slack, c.slack + c.var_slack, c.slack + c.dep_slack],
            np.float32,
        )

    def update(
        self,
        chunk_state: stats_lib.MomentState,
        *,
        chunk_index: int = 0,
        refit_index: int = 0,
        zs: Optional[Sequence[np.ndarray]] = None,
    ) -> List[DriftAlert]:
        """Score one chunk's moment summary; returns fired alerts.

        ``zs`` lets a batched caller (:func:`score_chunks_many`) hand
        in precomputed statistics; otherwise the shared jitted
        transform runs on this chunk alone.
        """
        if self.graph is None:
            raise RuntimeError("monitor not armed — no served graph yet")
        if float(chunk_state.count) < self.config.min_count:
            return []
        if zs is None:
            g = self.graph
            zs = chunk_drift_stats(
                chunk_state.count, chunk_state.mean, chunk_state.m2,
                jnp.asarray(g.a), jnp.asarray(g.intercept),
                jnp.asarray(g.b0), jnp.asarray(g.resid_var),
            )
        z = np.stack([np.asarray(v, np.float32) for v in zs])  # (3, d)
        slack = self._slacks()[:, None]
        self._pos = np.maximum(0.0, self._pos + z - slack)
        # Negative side only where a drop is meaningful: means can
        # shift down, variances can collapse; the dependence score is
        # one-sided (independence cannot get "more true" than true).
        self._neg[:2] = np.maximum(0.0, self._neg[:2] - z[:2] - slack[:2])
        self.n_scored += 1

        alerts: List[DriftAlert] = []
        level = np.maximum(self._pos, self._neg)
        h = self.config.threshold
        for s_idx, kind in enumerate(STAT_KINDS):
            for j in np.nonzero(level[s_idx] > h)[0]:
                key = (int(j), kind)
                last = self._last_alert.get(key)
                if last is not None and (
                    chunk_index - last
                ) <= self.config.cooldown:
                    continue
                self._last_alert[key] = chunk_index
                alerts.append(self._alert(
                    int(j), kind, float(level[s_idx, j] / h),
                    float(z[s_idx, j]), chunk_index, refit_index,
                ))
        if alerts:
            obs_metrics.inc(
                "monitor.alerts", len(alerts), sid=self.sid or "-",
            )
        obs_metrics.gauge(
            "monitor.drift_score", self.max_score(), sid=self.sid or "-",
        )
        return alerts

    def _alert(self, variable, kind, score, stat, chunk_index,
               refit_index) -> DriftAlert:
        from repro.infer import rca

        cands = rca.drift_root_candidates(
            self.graph.b0, self.graph.order, self.variable_scores(),
            top_k=self.config.rca_top_k,
        )
        obs_metrics.inc(
            "monitor.alerts_by_kind", kind=kind, sid=self.sid or "-",
        )
        return DriftAlert(
            sid=self.sid, variable=variable, kind=kind, score=score,
            stat=stat, chunk_index=chunk_index, refit_index=refit_index,
            candidate_roots=cands,
        )


def _batch_bucket(n: int) -> int:
    """Micro-batch bucket for the vmapped scorer: ``n`` on the blocked
    backend; elsewhere the next power of two, capped at ``n`` rounded up
    to 128."""
    from repro.core.batched import pow2_bucket
    from repro.kernels.tune import registry

    # The values a kernel block dispatcher used to pick; nothing states
    # why they differ by backend (ROADMAP, Queue 3).
    if registry.default_backend() == "blocked":
        return n
    return pow2_bucket(n, -(-n // 128) * 128)


def score_chunks_many(
    monitors: Sequence[GraphHealthMonitor],
    chunk_states: Sequence[stats_lib.MomentState],
    *,
    chunk_indices: Optional[Sequence[int]] = None,
) -> List[List[DriftAlert]]:
    """Score one chunk per monitor as a single padded vmapped program.

    All monitors must share ``(d, lags)`` (one compile per shape
    family; the engine groups sessions the same way it buckets refits).
    Padding repeats the first entry up to the micro-batch bucket, so a
    burst of concurrent sessions costs one device program instead of a
    per-session loop.
    """
    if not monitors:
        return []
    n = len(monitors)
    bucket = _batch_bucket(n)
    pad = bucket - n

    def stack(xs):
        xs = list(xs) + [xs[0]] * pad
        return jnp.stack([jnp.asarray(x) for x in xs])

    graphs = [m.graph for m in monitors]
    if any(g is None for g in graphs):
        raise RuntimeError("every monitor must be armed before batching")
    zs = _chunk_drift_stats_many(
        stack([s.count for s in chunk_states]),
        stack([s.mean for s in chunk_states]),
        stack([s.m2 for s in chunk_states]),
        stack([g.a for g in graphs]),
        stack([g.intercept for g in graphs]),
        stack([g.b0 for g in graphs]),
        stack([g.resid_var for g in graphs]),
    )
    z_mean, z_var, z_dep = (np.asarray(z) for z in zs)
    out: List[List[DriftAlert]] = []
    for i, (mon, state) in enumerate(zip(monitors, chunk_states)):
        idx = chunk_indices[i] if chunk_indices is not None else 0
        out.append(mon.update(
            state, chunk_index=idx,
            zs=(z_mean[i], z_var[i], z_dep[i]),
        ))
    return out


__all__ = [
    "DriftAlert",
    "GraphHealthMonitor",
    "MonitorConfig",
    "ServedGraph",
    "STAT_KINDS",
    "chunk_drift_stats",
    "score_chunks_many",
]
