"""Batched causal-discovery serving engine.

``CausalDiscoveryEngine`` serves DirectLiNGAM traffic:
fit requests are grouped by (m, d) shape, padded to a fixed micro-batch,
and executed through the functional core's batched engine
(``repro.core.batched.fit_many``) — one compile per dataset shape, then
every full micro-batch is a single device-parallel program.

The engine also admits *streaming* sessions (``open_stream`` /
``post_chunk`` / ``flush_streams``): each session owns a rolling-window
VarLiNGAM over the incremental moment store (:mod:`repro.stream`);
posted chunks advance the window in O(chunk d^2), and due refits across
sessions are bucketed by (residual shape, fit config) and executed
through ``batched.fit_many_from_stats`` — a burst of due windows costs
one device-parallel program, and each client gets back a
:class:`~repro.stream.session.GraphDelta` rather than the full matrix.
Monitored sessions (:mod:`repro.stream.monitor`) additionally score
every chunk against the served graph; drift alerts make a session due
immediately, ride out on its next delta, and are collectable through
:meth:`CausalDiscoveryEngine.poll_alerts`.

Fitted (or streaming) graphs are *queryable*: ``query`` admits a mixed
micro-batch of effect / intervention / root-cause requests
(:mod:`repro.infer.query`) and executes each (kind, shape) bucket as
one compiled device-parallel program; stream-session ids resolve to
the session's live estimate with moments from its incremental store.

The engine is instrumented with :mod:`repro.obs` (off by default):
spans around run/flush/query, histograms for queue wait, bucket fill,
and flush latency, and a deferral counter for the bounded-deferral
auto-flush rule. Per-session refit failures during a flush never abort
the batch — they surface as :class:`FlushError` records in
``last_flush_errors`` (telemetry on or off) and the failed sessions
stay due for retry.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import api as lingam_api
from repro.core import batched as lingam_batched
from repro.infer import query as query_lib
from repro.obs import metrics as obs_metrics
from repro.stream import session as stream_session
from repro.stream import window as stream_window


@dataclasses.dataclass
class FitRequest:
    """One causal-discovery request: a dataset to fit."""

    data: np.ndarray  # (m, d) float32
    result: Optional[lingam_api.FitResult] = None  # numpy-leaved on return


@dataclasses.dataclass
class FlushError:
    """One session's failed refit during :meth:`CausalDiscoveryEngine.
    flush_streams`, surfaced as data instead of aborting the flush.

    ``stage`` names where the failure happened: ``"prepare"`` (the
    session's refit plan could not be built), ``"fit"`` (the batched —
    or fallback per-session — fit program raised), or ``"finish"``
    (residual-variance finish / delta application). A failed session
    keeps its due state, so the next post or explicit flush retries it.
    """

    sid: str            # "*" for a whole-bucket program failure
    stage: str          # "prepare" | "fit" | "finish"
    bucket: Optional[Tuple[Tuple[int, ...], lingam_api.FitConfig]]
    error: Exception

    def summary(self) -> str:
        shape = None if self.bucket is None else self.bucket[0]
        return (
            f"flush error [{self.stage}] session={self.sid} "
            f"bucket={shape}: {type(self.error).__name__}: {self.error}"
        )


class CausalDiscoveryEngine:
    """Micro-batched DirectLiNGAM serving over the functional core.

    Requests with the same (m, d) shape share compiled programs. Two
    regimes, selected by the config's execution plan:

    * **vmap plan** (``config.partition is None``, the default): partial
      batches are padded (by repeating the first dataset) up to the next
      power-of-two bucket <= ``batch_size``, so a singleton request
      costs one fit — not ``batch_size`` fits — while the compile cache
      stays bounded at log2(batch_size) entries per dataset shape.
    * **mesh plan** (``config.partition`` set): each dataset is one
      ``shard_map`` program over the whole device mesh (all devices
      cooperate on a single fit — the d >> one-device regime), so
      requests run sequentially; the per-(m, d) shape bucket still
      reuses the sharded compile cache, which is what keeps mixed
      traffic from recompiling per request.

    Streaming traffic is the third regime: ``open_stream`` admits a
    session, ``post_chunk`` advances its rolling window (cheap — no
    fit), and due refits are *batched across sessions* on flush through
    ``fit_many_from_stats`` with the same shape-bucketed padding
    discipline as the one-shot path. ``post_chunk`` auto-flushes once a
    full micro-batch of sessions is due.

    ``warmup(shapes)`` pre-compiles the fit programs for the expected
    dataset shapes, so first requests pay no compile.
    """

    def __init__(self, config: Optional[lingam_api.FitConfig] = None,
                 *, batch_size: int = 8,
                 warmup_shapes: Optional[List[Tuple[int, int]]] = None):
        self.config = config or lingam_api.FitConfig(compaction="staged")
        self.batch_size = batch_size
        self._streams: Dict[str, stream_session.StreamSession] = {}
        self._next_sid = 0
        # Errors from the most recent flush_streams call (always kept,
        # telemetry on or off) — empty means every due refit landed.
        # Bounded: a pathological flush over many sessions cannot grow
        # the error record without limit (drops are counted).
        self.last_flush_errors: obs.BoundedRing = obs.BoundedRing(256)
        self.queries = query_lib.QueryEngine(batch_size=batch_size)
        if warmup_shapes:
            self.warmup(warmup_shapes)

    def warmup(self, shapes: List[Tuple[int, int]]) -> None:
        """Pre-compile the vmap fit program for each (m, d) dataset shape
        this engine expects, so first requests pay no compile. Returns
        None. The mesh plan (``config.partition`` set) compiles on its
        first request."""
        if self.config.partition is None:
            for shape in shapes:
                lingam_batched.warmup_fit_many(shape, self.config)

    def _bucket(self, n: int) -> int:
        return lingam_batched.pow2_bucket(n, self.batch_size)

    def _run_mesh(self, group: List[FitRequest]) -> None:
        """Mesh plan: one sharded full-fit program per dataset; the
        (m, d)-keyed compile cache lives in ``core.sharded``."""
        for r in group:
            res = lingam_api.fit_fn(
                jnp.asarray(np.asarray(r.data, np.float32)), self.config
            )
            r.result = lingam_api.FitResult(
                order=np.asarray(res.order),
                adjacency=np.asarray(res.adjacency),
                resid_var=np.asarray(res.resid_var),
            )

    def run(self, requests: List[FitRequest]) -> List[FitRequest]:
        with obs.span("serve.run", n=len(requests)):
            by_shape = {}
            for r in requests:
                by_shape.setdefault(np.asarray(r.data).shape, []).append(r)
            for shape, group in by_shape.items():
                if self.config.partition is not None:
                    self._run_mesh(group)
                    continue
                for start in range(0, len(group), self.batch_size):
                    chunk = group[start:start + self.batch_size]
                    self._run_fit_bucket(shape, chunk)
            obs_metrics.inc("serve.fit_requests", len(requests))
        return requests

    def _run_fit_bucket(self, shape, chunk: List[FitRequest]) -> None:
        bucket = self._bucket(len(chunk))
        with obs.span(
            "serve.fit_bucket", shape=shape, n=len(chunk), bucket=bucket
        ):
            t0 = time.perf_counter()
            xs = np.stack(
                [np.asarray(r.data, np.float32) for r in chunk]
                + [np.asarray(chunk[0].data, np.float32)]
                * (bucket - len(chunk))
            )
            results = lingam_batched.fit_many(
                jnp.asarray(xs), self.config
            )
            order = np.asarray(results.order)
            adj = np.asarray(results.adjacency)
            rv = np.asarray(results.resid_var)
            for i, r in enumerate(chunk):
                r.result = lingam_api.FitResult(
                    order=order[i], adjacency=adj[i], resid_var=rv[i]
                )
            obs_metrics.observe(
                "serve.bucket_fill", len(chunk) / bucket, kind="fit"
            )
            obs_metrics.observe(
                "serve.fit_bucket_s", time.perf_counter() - t0,
                m=shape[0], d=shape[1],
            )

    # ------------------------------------------------------------------
    # Streaming sessions
    # ------------------------------------------------------------------

    def open_stream(
        self, config: stream_session.StreamConfig
    ) -> str:
        """Admit a streaming session; returns its session id."""
        sid = f"stream-{self._next_sid}"
        self._next_sid += 1
        self._streams[sid] = stream_session.StreamSession(sid, config)
        return sid

    def post_chunk(
        self, sid: str, rows
    ) -> List[Tuple[str, stream_session.GraphDelta]]:
        """Advance a session's window by one chunk (O(chunk d^2), no
        fit). Auto-flushes — returning (sid, delta) pairs — once a full
        micro-batch of sessions is due, counting only sessions whose
        windows are full (a still-filling session cannot become due
        without its own posts, so it must not starve the active ones).
        A due refit is deferred at most one of its session's own posts
        waiting for peers to join the batch: if this session was
        already due *before* this post, the flush happens now, so a
        ready-but-idle peer delays an active client by one chunk at
        worst. Returns [] when nothing flushed (call
        :meth:`flush_streams` to force pending refits out)."""
        session = self._streams[sid]
        was_due = session.due
        session.post(rows)
        n_due = sum(1 for s in self._streams.values() if s.due)
        n_ready = sum(
            1 for s in self._streams.values() if s.rolling.ready
        )
        if n_due and (was_due or n_due >= min(self.batch_size, n_ready)):
            return self.flush_streams()
        if session.due:
            # This post left its session due but waiting for bucket
            # peers — the one-chunk deferral the auto-flush rule allows.
            obs_metrics.inc("serve.flush_deferrals", sid=sid)
        return []

    def flush_streams(self) -> List[Tuple[str, stream_session.GraphDelta]]:
        """Execute every due session's refit, batched.

        Due sessions' :class:`~repro.stream.window.RefitPlan`s are
        bucketed by (residual shape, fit config); each bucket is padded
        to the power-of-two micro-batch and run as one
        ``fit_many_from_stats`` program — the streaming analogue of
        :meth:`run`'s shape bucketing.

        A failing session does **not** abort the flush: its error is
        recorded as a :class:`FlushError` in ``last_flush_errors`` (and
        counted in ``serve.flush_errors`` when telemetry is on), the
        remaining sessions proceed, and the failed session stays due so
        the next post or flush retries it. A whole-bucket program
        failure falls back to per-session refits, so one poisoned plan
        cannot starve its bucket peers.
        """
        self.last_flush_errors.clear()
        t_flush = time.perf_counter()
        due = [
            (sid, s) for sid, s in self._streams.items() if s.due
        ]
        out: List[Tuple[str, stream_session.GraphDelta]] = []
        with obs.span("serve.flush", n_due=len(due)):
            now = time.monotonic()
            for sid, s in due:
                waited = s.due_wait_s(now)
                if waited is not None:
                    obs_metrics.observe("serve.queue_wait_s", waited)
            buckets: Dict[object, List] = {}
            for sid, s in due:
                try:
                    plan = s.rolling.prepare_refit()
                except Exception as e:  # noqa: BLE001 — surfaced as data
                    self._flush_error(sid, "prepare", None, e)
                    continue
                key = stream_session.bucket_key(s, plan)
                buckets.setdefault(key, []).append((sid, s, plan))
            for (shape, config), group in buckets.items():
                for start in range(0, len(group), self.batch_size):
                    part = group[start:start + self.batch_size]
                    out.extend(self._flush_bucket(shape, config, part))
            obs_metrics.observe(
                "serve.flush_s", time.perf_counter() - t_flush
            )
            obs_metrics.inc("serve.flushes")
        return out

    def _flush_bucket(
        self, shape, config, part
    ) -> List[Tuple[str, stream_session.GraphDelta]]:
        """One padded ``fit_many_from_stats`` micro-batch of due
        sessions, with per-session error isolation."""
        bucket = self._bucket(len(part))
        pad = bucket - len(part)
        plans = [p for _, _, p in part] + [part[0][2]] * pad
        out: List[Tuple[str, stream_session.GraphDelta]] = []
        with obs.span(
            "serve.flush_bucket", shape=shape, n=len(part), bucket=bucket
        ):
            obs_metrics.observe(
                "serve.bucket_fill", len(part) / bucket, kind="flush"
            )
            try:
                results = lingam_batched.fit_many_from_stats(
                    jnp.stack([p.resid for p in plans]),
                    jnp.stack([p.resid_mean for p in plans]),
                    jnp.stack([p.resid_cov for p in plans]),
                    config,
                )
                order = np.asarray(results.order)
                adj = np.asarray(results.adjacency)
                rv = np.asarray(results.resid_var)
            except Exception as e:  # noqa: BLE001 — surfaced as data
                self._flush_error("*", "fit", (shape, config), e)
                for sid, s, _ in part:
                    try:
                        out.append((sid, s.refit_now()))
                    except Exception as e2:  # noqa: BLE001
                        self._flush_error(sid, "fit", (shape, config), e2)
                return out
            for i, (sid, s, plan) in enumerate(part):
                try:
                    fit = stream_window.finish_refit(
                        plan,
                        lingam_api.FitResult(
                            order=order[i], adjacency=adj[i],
                            resid_var=rv[i],
                        ),
                    )
                    out.append((sid, s.apply_fit(fit)))
                except Exception as e:  # noqa: BLE001
                    self._flush_error(sid, "finish", (shape, config), e)
        return out

    def _flush_error(self, sid, stage, bucket, error) -> None:
        err = FlushError(sid=sid, stage=stage, bucket=bucket, error=error)
        self.last_flush_errors.append(err)
        obs_metrics.inc("serve.flush_errors", sid=sid, stage=stage)

    # ------------------------------------------------------------------
    # Causal queries (effects / interventions / RCA)
    # ------------------------------------------------------------------

    def query(self, queries: List[object]) -> List[object]:
        """Answer a micro-batch of causal queries against fitted graphs.

        Accepts a mixed list of :class:`repro.infer.query.EffectQuery` /
        ``InterventionQuery`` / ``RCAQuery``. Each request's ``graph``
        may be a :class:`~repro.infer.query.FittedGraph`, a bare
        :class:`~repro.core.api.FitResult` (wrapped with centered-data
        defaults), or a *stream session id* — resolved here to the
        session's current estimate with observational moments pulled
        from its incremental store (no rows re-read). Execution is
        delegated to the :class:`~repro.infer.query.QueryEngine`:
        bucketed by (kind, shape), padded to the power-of-two
        micro-batch, one compiled device-parallel program per bucket.

        Session-backed graphs are re-snapshotted from the *live*
        session on every call (the resolved ``FittedGraph`` remembers
        its ``sid``), so a client that re-issues the same query object
        after more posts sees the current estimate, never a stale one.
        """
        with obs.span("serve.query", n=len(queries)):
            for q in queries:
                sid = (
                    q.graph if isinstance(q.graph, str)
                    else getattr(q.graph, "sid", None)
                )
                if sid is not None:
                    q.graph = query_lib.FittedGraph.from_session(
                        self._streams[sid]
                    )
            return self.queries.run(queries)

    def poll_alerts(
        self, sid: Optional[str] = None
    ) -> List[stream_session.monitor_lib.DriftAlert]:
        """Drain unread drift alerts, oldest first.

        ``sid`` scopes the drain to one session; None collects across
        every admitted session. Each alert is delivered exactly once
        here — the session's bounded ``alert_history`` keeps a copy for
        post-hoc review, and alerts that *triggered* a refit also
        travel on that refit's :class:`~repro.stream.session.GraphDelta`
        from :meth:`flush_streams`. Sessions without a monitor simply
        never yield alerts.
        """
        sessions = (
            [self._streams[sid]] if sid is not None
            else list(self._streams.values())
        )
        out: List[stream_session.monitor_lib.DriftAlert] = []
        for s in sessions:
            out.extend(s.unread_alerts.drain())
        if out:
            obs_metrics.inc("serve.alerts_polled", len(out))
        return out

    def stream_session(self, sid: str) -> stream_session.StreamSession:
        """The live session object (last_fit / last_delta / state)."""
        return self._streams[sid]

    def close_stream(self, sid: str) -> stream_session.StreamSession:
        """Retire a session, returning its final state."""
        return self._streams.pop(sid)
