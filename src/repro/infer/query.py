"""Query engine: micro-batched causal queries over fitted graphs.

A fitted graph should be a queryable object, not a matrix dump. This
module gives serving traffic that object:

  * :class:`FittedGraph` — a :class:`~repro.core.api.FitResult` plus
    the observational context queries need (data mean, structural-noise
    moments), buildable from a one-shot fit (:meth:`FittedGraph.
    from_result`) or a live streaming session (:meth:`FittedGraph.
    from_session` — moments come from the session's incremental store,
    no rows re-read).
  * :class:`EffectQuery` / :class:`InterventionQuery` /
    :class:`RCAQuery` — the three request kinds.
  * :class:`QueryEngine` — admits a mixed list of requests, buckets
    them by (query kind, graph shape), pads each bucket to the
    power-of-two micro-batch, and executes it as **one** compiled
    device-parallel program (``jit(vmap(...))`` over the bucket).
    Compilation happens once per (kind, shape) signature — recorded in
    the public compile log (``repro.obs.compile_log``, ops
    ``query.effects`` / ``query.intervention`` / ``query.rca``) and
    pinned by ``tests/test_infer.py`` — so steady-state traffic never
    traces. Per-(kind, shape) bucket latencies land in
    ``repro.obs.metrics`` (series ``query.bucket_s``) when telemetry
    is enabled.

Interventions use dense (d,) do-masks (:func:`repro.infer.intervene.
do_arrays`), so requests targeting *different* variables still share a
bucket. The serving side
(:meth:`repro.serve.engine.CausalDiscoveryEngine.query`) resolves
stream-session ids to :class:`FittedGraph`\\ s and delegates here.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Mapping, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import api, batched
from repro.obs import compile_log
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile

from . import effects as effects_lib
from . import intervene as intervene_lib
from . import rca as rca_lib

# Each batch kernel records its trace body in the public compile log
# (one event per (kind, shape-bucket) signature, never in steady state)
# — the single-compile contract tests/test_infer.py pins through
# repro.obs.compile_log.


@jax.jit
def _effects_batch(adj, order):
    compile_log.record("query.effects", shape=adj.shape)
    return jax.vmap(effects_lib.total_effects_impl)(adj, order)


@jax.jit
def _intervene_batch(adj, order, mask, values, noise_mean, noise_var):
    compile_log.record("query.intervention", shape=adj.shape)
    mu = jax.vmap(intervene_lib.interventional_mean_impl)(
        adj, order, mask, values, noise_mean
    )
    cov = jax.vmap(intervene_lib.interventional_cov_impl)(
        adj, order, mask, noise_var
    )
    return mu, cov


@jax.jit
def _rca_batch(adj, order, rows, mean, resid_var, target):
    compile_log.record("query.rca", shape=rows.shape)
    scores = jax.vmap(rca_lib.noise_scores_impl)(adj, rows, mean, resid_var)
    contrib = jax.vmap(rca_lib.contributions_impl)(
        adj, order, rows, mean, target
    )
    return scores, contrib


@dataclasses.dataclass
class FittedGraph:
    """A fitted graph plus the observational context queries consume."""

    result: api.FitResult
    mean: np.ndarray        # (d,) observational mean of the fitted space
    noise_mean: np.ndarray  # (d,) E[e] implied by the moments
    noise_var: np.ndarray   # (d,) Var e (resid_var unless moments given)
    sid: Optional[str] = None  # originating stream session, if any: the
    #                            serving engine re-snapshots live sessions
    #                            on every query, so re-issued requests
    #                            never answer from a stale estimate

    @property
    def d(self) -> int:
        return int(self.result.order.shape[0])

    @classmethod
    def from_result(cls, result: api.FitResult, *, mean=None, cov=None
                    ) -> "FittedGraph":
        """Wrap a one-shot fit. ``mean``/``cov`` are the training data's
        observational moments; omitted, the data is taken as centered
        and the noise variances fall back to ``resid_var``."""
        d = int(result.order.shape[0])
        mu = (np.zeros((d,), np.float32) if mean is None
              else np.asarray(mean, np.float32))
        if cov is None:
            r = np.eye(d, dtype=np.float32) - np.asarray(result.adjacency)
            nm = r @ mu
            nv = np.asarray(result.resid_var, np.float32)
        else:
            nm_j, nv_j = intervene_lib.noise_stats(
                jnp.asarray(result.adjacency), jnp.asarray(mu),
                jnp.asarray(cov),
            )
            nm, nv = np.asarray(nm_j), np.asarray(nv_j)
        return cls(result=result, mean=mu, noise_mean=nm, noise_var=nv)

    @classmethod
    def from_session(cls, session) -> "FittedGraph":
        """Wrap a streaming session's current estimate.

        The instantaneous graph ``B0`` comes from the session's last
        refit; the observational mean is the rolling window's (sliced
        from the lag-augmented moment store — no rows re-read), and the
        noise statistics are ``(I - B0) mu`` with the refit's residual
        variances. Queries thus describe the *contemporaneous* SEM at
        the window's operating point: RCA rows should be deviations of
        raw samples (the lag-driven part shows up in the noise terms),
        and interventional moments are contemporaneous-equilibrium
        answers, not multi-step forecasts (use
        :func:`repro.infer.effects.var_irf` for lag propagation).
        """
        if session.last_fit is None:
            raise ValueError(
                f"session {session.sid!r} has no estimate yet "
                "(window not full or no refit flushed)"
            )
        result = session.last_fit.result
        d = int(result.order.shape[0])
        state = session.rolling.aug_state
        mu = np.asarray(state.mean, np.float32)[:d]
        r = np.eye(d, dtype=np.float32) - np.asarray(result.adjacency)
        return cls(
            result=result,
            mean=mu,
            noise_mean=r @ mu,
            noise_var=np.asarray(result.resid_var, np.float32),
            sid=session.sid,
        )


GraphRef = Union["FittedGraph", api.FitResult, str]


@dataclasses.dataclass
class EffectQuery:
    """Total-effect matrix of one graph. Answer: ``effects`` (d, d)."""

    graph: GraphRef
    effects: Optional[np.ndarray] = None


@dataclasses.dataclass
class InterventionQuery:
    """Post-intervention moments under ``do``. Answer: ``mean`` (d,),
    ``cov`` (d, d)."""

    graph: GraphRef
    do: Mapping[int, float] = dataclasses.field(default_factory=dict)
    mean: Optional[np.ndarray] = None
    cov: Optional[np.ndarray] = None


@dataclasses.dataclass
class RCAQuery:
    """Root-cause attribution of ``rows``. Answer: ``result``
    (:class:`repro.infer.rca.RCAResult`)."""

    graph: GraphRef
    rows: np.ndarray = None
    target: Optional[int] = None
    result: Optional[rca_lib.RCAResult] = None


class QueryEngine:
    """Shape-bucketed, micro-batched execution of causal queries.

    Mixed request lists are grouped by (kind, d) — RCA additionally by
    its row-batch length — padded to the next power-of-two bucket
    (<= ``batch_size``, by repeating the first request's graph, so a
    singleton costs one query, not ``batch_size``), and each bucket
    runs as a single ``jit(vmap(...))`` program. The compile cache is
    keyed by the bucket signature, so a steady query mix compiles once
    per (kind, shape) and never again.
    """

    def __init__(self, *, batch_size: int = 8):
        self.batch_size = batch_size

    def _bucket(self, n: int) -> int:
        return batched.pow2_bucket(n, self.batch_size)

    @staticmethod
    def _resolve(q) -> FittedGraph:
        if isinstance(q.graph, api.FitResult):
            q.graph = FittedGraph.from_result(q.graph)
        if not isinstance(q.graph, FittedGraph):
            raise TypeError(
                f"unresolved graph ref {type(q.graph).__name__}: string "
                "session ids are resolved by CausalDiscoveryEngine.query"
            )
        return q.graph

    def run(self, queries: List[object]) -> List[object]:
        buckets: Dict[object, List[object]] = {}
        for q in queries:
            g = self._resolve(q)
            if isinstance(q, EffectQuery):
                key = ("effects", g.d)
            elif isinstance(q, InterventionQuery):
                key = ("intervention", g.d)
            elif isinstance(q, RCAQuery):
                rows = np.asarray(q.rows, np.float32)
                q.rows = rows[None, :] if rows.ndim == 1 else rows
                key = ("rca", g.d, q.rows.shape[0])
            else:
                raise TypeError(f"unknown query type {type(q).__name__}")
            buckets.setdefault(key, []).append(q)
        for key, group in buckets.items():
            runner = getattr(self, f"_run_{key[0]}")
            with obs.span(
                "query.bucket", kind=key[0], d=key[1], n=len(group)
            ):
                t0 = time.perf_counter()
                for start in range(0, len(group), self.batch_size):
                    part = group[start:start + self.batch_size]
                    runner(
                        part
                        + [part[0]] * (self._bucket(len(part)) - len(part))
                    )
                obs_metrics.observe(
                    "query.bucket_s", time.perf_counter() - t0,
                    kind=key[0], d=key[1],
                )
                obs_metrics.inc("query.requests", len(group), kind=key[0])
        return queries

    @staticmethod
    def _stack_graphs(part):
        gs = [q.graph for q in part]
        adj = jnp.stack([jnp.asarray(g.result.adjacency) for g in gs])
        order = jnp.stack([jnp.asarray(g.result.order) for g in gs])
        return gs, adj, order

    def _run_effects(self, part):
        _, adj, order = self._stack_graphs(part)
        out = np.asarray(obs_profile.call(
            _effects_batch, adj, order,
            op="query.effects", shape=adj.shape,
        ))
        for i, q in enumerate(part):
            q.effects = out[i]

    def _run_intervention(self, part):
        gs, adj, order = self._stack_graphs(part)
        d = gs[0].d
        masks, values = zip(*(intervene_lib.do_arrays(d, q.do) for q in part))
        mu, cov = obs_profile.call(
            _intervene_batch, adj, order,
            jnp.asarray(np.stack(masks)), jnp.asarray(np.stack(values)),
            jnp.asarray(np.stack([g.noise_mean for g in gs])),
            jnp.asarray(np.stack([g.noise_var for g in gs])),
            op="query.intervention", shape=adj.shape,
        )
        mu, cov = np.asarray(mu), np.asarray(cov)
        for i, q in enumerate(part):
            q.mean, q.cov = mu[i], cov[i]

    def _run_rca(self, part):
        gs, adj, order = self._stack_graphs(part)
        rows = np.stack([q.rows for q in part])  # (b, n, d)
        _, n, d = rows.shape
        # Heavy reduction: the per-program row slab is RCA's default
        # sample slab — padded (zero rows, trimmed below) so ragged
        # tails reuse a bounded set of compiles.
        slab = rca_lib.SAMPLE_SLAB
        targets = jnp.asarray(
            [0 if q.target is None else int(q.target) for q in part],
            jnp.int32,
        )
        means = jnp.asarray(np.stack([g.mean for g in gs]))
        noise_var = jnp.asarray(np.stack([g.noise_var for g in gs]))
        scores_parts, contrib_parts = [], []
        for start in range(0, n, slab):
            block = rows[:, start:start + slab]
            k = block.shape[1]
            padded = jnp.asarray(rca_lib._pad_rows(block, slab, axis=1))
            s, c = obs_profile.call(
                _rca_batch, adj, order, padded, means, noise_var, targets,
                op="query.rca", shape=padded.shape,
            )
            scores_parts.append(np.asarray(s)[:, :k])
            contrib_parts.append(np.asarray(c)[:, :k])
        scores = np.concatenate(scores_parts, axis=1)
        contrib = np.concatenate(contrib_parts, axis=1)
        for i, q in enumerate(part):
            q.result = rca_lib.RCAResult(
                scores=scores[i],
                root=np.argmax(np.abs(scores[i]), axis=1),
                target=q.target,
                contributions=contrib[i] if q.target is not None else None,
            )
