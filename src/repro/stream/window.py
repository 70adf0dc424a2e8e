"""Rolling-window VarLiNGAM over the incremental moment store.

The paper's §4.2 stock panels are time series: consecutive analysis
windows share almost all of their rows, yet a from-scratch refit pays
the full cost of the window every slide — the VAR least squares, the
covariance matmuls, the standardization passes. Here a window slides in
*chunks*:

  * :class:`ChunkRing` — fixed-capacity ring of (chunk, d) row blocks;
    pushing into a full ring evicts (and returns) the oldest block.
  * :class:`RollingVarLiNGAM` — maintains a :class:`~repro.stream.stats.
    MomentState` over the window's *lag-augmented* rows
    ``[x_t, x_{t-1}, ..., x_{t-k}]``: each slide absorbs the new
    chunk's augmented rows and retracts the expired one's
    (O(chunk d^2)), instead of rescanning the window. A refit then
    reads the data only where it must:

      - VAR(k) coefficients come from the merged covariance blocks
        (one (kd, kd) solve — no O(m (kd)^2) lstsq over the window);
      - VAR residuals are materialized chunk-by-chunk (one small GEMM
        per live block);
      - the DirectLiNGAM step runs through ``api.fit_from_stats`` with
        the residual mean/covariance derived from the same state, so
        standardization, pruning, and diagnostics skip their data
        passes; only the nonlinear ordering moments re-read the rows,
        chunk-bounded via ``FitConfig.moment_chunk``.

:func:`direct_window_fit` is the from-scratch oracle: the identical
estimator computed from a direct two-pass over the whole window (no
merges, no retractions). ``tests/test_stream.py`` pins rolling == direct
within fp32 tolerance; ``benchmarks/bench_stream.py`` records the
per-slide speedup against it and against the legacy lstsq path.
"""

from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api
from repro.obs import metrics as obs_metrics
from . import stats

_RIDGE = 1e-6


def lagged_rows(buf: np.ndarray, lags: int) -> np.ndarray:
    """Lag-augmented rows of a contiguous (n, d) block.

    Row t (for t in [lags, n)) is ``[x_t, x_{t-1}, ..., x_{t-lags}]``,
    shape (n - lags, (lags + 1) d) — the first ``lags`` rows of ``buf``
    serve only as history. A chunk pushed with its predecessor's
    ``lags``-row tail therefore contributes exactly ``chunk`` augmented
    rows; the stream's very first chunk contributes ``chunk - lags``.
    """
    n = buf.shape[0]
    if n <= lags:
        raise ValueError(f"need more than lags={lags} rows, got {n}")
    return np.concatenate(
        [buf[lags - tau : n - tau] for tau in range(lags + 1)], axis=1
    )


class ChunkRing:
    """Fixed-capacity FIFO ring of (chunk, d) row blocks.

    ``push`` returns the evicted oldest block once the ring is full
    (None before that). Iteration runs oldest -> newest.
    """

    def __init__(self, capacity: int):
        if capacity < 2:
            raise ValueError(f"ring needs >= 2 chunks, got {capacity}")
        self.capacity = capacity
        self._blocks: deque = deque()

    def push(self, rows: np.ndarray) -> Optional[np.ndarray]:
        self._blocks.append(rows)
        if len(self._blocks) > self.capacity:
            return self._blocks.popleft()
        return None

    @property
    def full(self) -> bool:
        return len(self._blocks) == self.capacity

    def __len__(self) -> int:
        return len(self._blocks)

    def __iter__(self):
        return iter(self._blocks)


@functools.partial(jax.jit, static_argnames=("d", "lags"))
def _var_solve(count, mean, m2, *, d: int, lags: int):
    """VAR(k) + residual stats from augmented-row moments.

    The augmented covariance's blocks are the normal equations of the
    windowed regression y = x_t on z = [x_(t-1), ..., x_(t-k)] with
    intercept: A = Cov(y, z) Cov(z, z)^-1 (tiny ridge for safety),
    intercept = mean_y - A mean_z, Cov(resid) = Cov(y) - A Cov(z, y)
    (exact at the solution; the residual mean is 0 by construction).
    Returns (a, mats, intercept, resid_cov) with ``a`` the (d, k d)
    stacked coefficient rows and ``mats`` its [k, d, d] per-lag view.
    """
    cov = m2 / jnp.maximum(count, 1.0)
    szz = cov[d:, d:]
    szy = cov[d:, :d]
    ridge = _RIDGE * jnp.mean(jnp.diagonal(szz)) + 1e-30
    szz = szz + ridge * jnp.eye(szz.shape[0], dtype=szz.dtype)
    a = jnp.linalg.solve(szz, szy).T  # (d, k d)
    intercept = mean[:d] - a @ mean[d:]
    mats = a.reshape(d, lags, d).transpose(1, 0, 2)  # [k, d, d]
    resid_cov = cov[:d, :d] - a @ szy
    resid_cov = 0.5 * (resid_cov + resid_cov.T)
    return a, mats, intercept, resid_cov


@jax.jit
def _residual_block(aug, a, intercept):
    """VAR residuals of one augmented block: y - intercept - z A^T."""
    d = intercept.shape[0]
    y = aug[:, :d]
    z = aug[:, d:]
    return y - intercept[None, :] - z @ a.T


@dataclasses.dataclass
class RefitPlan:
    """One due refit, ready for (batched) execution: the window's VAR
    residuals plus the moment-derived statistics ``fit_from_stats`` /
    ``fit_many_from_stats`` consume."""

    resid: jax.Array       # (m_aug, d) window VAR residuals
    resid_mean: jax.Array  # (d,) zeros — exact with the intercept
    resid_cov: jax.Array   # (d, d) state-derived residual covariance
    mats: np.ndarray       # [k, d, d] VAR coefficient matrices
    intercept: np.ndarray  # (d,)


@dataclasses.dataclass
class RollingFit:
    """One window's estimate: the instantaneous fit + lagged thetas."""

    result: api.FitResult       # order/adjacency(B0)/resid_var
    thetas: List[np.ndarray]    # [theta_0 (= B0), theta_1, ..., theta_k]
    var_coefs: np.ndarray       # [k, d, d] raw VAR coefficients
    n_rows: int                 # augmented rows in the window
    intercept: Optional[np.ndarray] = None  # (d,) VAR intercept — the
    #                             served-graph parameter the drift
    #                             monitor needs to score new chunks


def finish_refit(plan: RefitPlan, result: api.FitResult) -> RollingFit:
    """Lagged-coefficient transform theta_tau = (I - B0) M_tau."""
    b0 = np.asarray(result.adjacency)
    eye = np.eye(b0.shape[0], dtype=b0.dtype)
    mats = np.asarray(plan.mats)
    thetas = [b0] + [
        np.asarray((eye - b0) @ mats[tau]) for tau in range(mats.shape[0])
    ]
    return RollingFit(
        result=result,
        thetas=thetas,
        var_coefs=mats,
        n_rows=int(plan.resid.shape[0]),
        intercept=np.asarray(plan.intercept),
    )


class RollingVarLiNGAM:
    """Incremental VarLiNGAM over a chunked rolling window.

    Args:
      d:             number of variables.
      chunk:         rows per pushed block (must exceed ``lags``).
      window_chunks: window length in chunks (ring capacity).
      lags:          VAR order k.
      config:        the DirectLiNGAM :class:`~repro.core.api.FitConfig`
                     for the residual fit; ``moment_chunk`` defaults to
                     the stream chunk (the session's memory bound), so
                     the ordering moments accumulate one chunk at a
                     time.
      reanchor_every: if > 0, rebuild the moment state from the live
                     ring every that-many slides (post window fill) to
                     cap retraction drift on non-stationary streams.
    """

    def __init__(
        self,
        d: int,
        chunk: int,
        window_chunks: int,
        *,
        lags: int = 1,
        config: api.FitConfig = api.FitConfig(compaction="staged"),
        reanchor_every: int = 0,
    ):
        if lags < 1:
            raise ValueError(f"lags must be >= 1, got {lags}")
        if chunk <= lags:
            raise ValueError(f"chunk ({chunk}) must exceed lags ({lags})")
        if config.partition is not None:
            raise ValueError(
                "RollingVarLiNGAM refits through the local/vmap plans; "
                "drop config.partition (use VarLiNGAM + fit_fn for the "
                "mesh plan)."
            )
        self.d = d
        self.chunk = chunk
        self.lags = lags
        self.reanchor_every = reanchor_every
        if config.moment_chunk is None:
            config = dataclasses.replace(config, moment_chunk=chunk)
        self.config = config
        self.ring = ChunkRing(window_chunks)
        self.aug_state = stats.init((lags + 1) * d)
        self._prev_tail: Optional[np.ndarray] = None  # newest chunk's tail
        self._lead_tail: Optional[np.ndarray] = None  # rows before oldest
        self.n_pushed = 0

    @property
    def ready(self) -> bool:
        """Whether a full window is buffered (refits allowed)."""
        return self.ring.full

    def push(self, rows) -> stats.MomentState:
        """Slide the window by one chunk: absorb ``rows``' augmented
        moments, retract the evicted chunk's.

        Returns the absorbed chunk's own augmented :class:`~repro.
        stream.stats.MomentState` — the summary this slide computed
        anyway (``update_chunk`` is ``merge(state, from_chunk(rows))``
        unrolled). The drift monitor scores served graphs from exactly
        this object, so monitoring never re-reads the chunk's rows.
        """
        # Copy unconditionally: the ring and tails hold these rows until
        # retraction, so aliasing a caller-reused buffer would silently
        # corrupt the window.
        rows = np.array(rows, dtype=np.float32, copy=True)
        if rows.shape != (self.chunk, self.d):
            raise ValueError(
                f"expected ({self.chunk}, {self.d}) rows, got {rows.shape}"
            )
        buf = rows if self._prev_tail is None else np.concatenate(
            [self._prev_tail, rows]
        )
        chunk_state = stats.from_chunk(
            jnp.asarray(lagged_rows(buf, self.lags))
        )
        self.aug_state = stats.merge(self.aug_state, chunk_state)
        evicted = self.ring.push(rows)
        if evicted is not None:
            ebuf = evicted if self._lead_tail is None else np.concatenate(
                [self._lead_tail, evicted]
            )
            self.aug_state = stats.retract_chunk(
                self.aug_state, lagged_rows(ebuf, self.lags)
            )
            self._lead_tail = evicted[-self.lags:]
            obs_metrics.inc("stream.retracts")
        self._prev_tail = rows[-self.lags:]
        self.n_pushed += 1
        if (
            self.reanchor_every
            and self.ring.full
            and self.n_pushed % self.reanchor_every == 0
        ):
            self.reanchor()
        return chunk_state

    def _window_bufs(self):
        """Live blocks with their lag context, oldest -> newest."""
        tail = self._lead_tail
        for block in self.ring:
            yield block if tail is None else np.concatenate([tail, block])
            tail = block[-self.lags:]

    def reanchor(self) -> None:
        """Rebuild the moment state from the live ring (drops all
        accumulated merge/retract rounding)."""
        state = stats.init((self.lags + 1) * self.d)
        for buf in self._window_bufs():
            state = stats.update_chunk(state, lagged_rows(buf, self.lags))
        self.aug_state = state
        obs_metrics.inc("stream.reanchors")

    def prepare_refit(self) -> RefitPlan:
        """Assemble this window's refit inputs (state-derived VAR +
        chunk-wise residual blocks); execution happens in
        :meth:`refit` or batched across sessions by the engine."""
        if not self.ready:
            raise RuntimeError(
                f"window not full: {len(self.ring)}/{self.ring.capacity} "
                "chunks buffered"
            )
        a, mats, intercept, resid_cov = _var_solve(
            self.aug_state.count,
            self.aug_state.mean,
            self.aug_state.m2,
            d=self.d,
            lags=self.lags,
        )
        blocks = [
            _residual_block(jnp.asarray(lagged_rows(buf, self.lags)), a,
                            intercept)
            for buf in self._window_bufs()
        ]
        return RefitPlan(
            resid=jnp.concatenate(blocks, axis=0),
            resid_mean=jnp.zeros((self.d,), jnp.float32),
            resid_cov=resid_cov,
            mats=np.asarray(mats),
            intercept=np.asarray(intercept),
        )

    def refit(self) -> RollingFit:
        """Re-estimate the current window's graph (single-session path;
        the serving engine batches many sessions' plans instead)."""
        plan = self.prepare_refit()
        result = api.fit_from_stats(
            plan.resid, plan.resid_mean, plan.resid_cov, self.config
        )
        return finish_refit(plan, result)


def direct_window_fit(
    chunks,
    lead_tail,
    *,
    lags: int = 1,
    config: api.FitConfig = api.FitConfig(compaction="staged"),
) -> RollingFit:
    """From-scratch oracle: the identical estimator via a direct
    two-pass over the whole window.

    Augmented rows are built in one piece, their moments computed with
    no merges or retractions, then the same VAR solve / residual /
    ``fit_from_stats`` tail runs. The rolling path must agree with this
    within fp32 merge tolerance — the parity the tests pin.
    """
    chunks = [np.ascontiguousarray(c, dtype=np.float32) for c in chunks]
    d = chunks[0].shape[1]
    buf = np.concatenate(
        ([lead_tail] if lead_tail is not None else []) + chunks
    )
    aug = lagged_rows(buf, lags)
    state = stats.from_chunk(jnp.asarray(aug))
    a, mats, intercept, resid_cov = _var_solve(
        state.count, state.mean, state.m2, d=d, lags=lags
    )
    resid = _residual_block(jnp.asarray(aug), a, intercept)
    plan = RefitPlan(
        resid=resid,
        resid_mean=jnp.zeros((d,), jnp.float32),
        resid_cov=resid_cov,
        mats=np.asarray(mats),
        intercept=np.asarray(intercept),
    )
    result = api.fit_from_stats(
        plan.resid, plan.resid_mean, plan.resid_cov, config
    )
    return finish_refit(plan, result)
