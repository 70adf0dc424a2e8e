"""Root-cause attribution by structural-noise decomposition.

In a LiNGAM SEM an observed sample decomposes *exactly* into its
exogenous noise terms: ``x - mu = A e~`` with ``A = (I - B)^{-1}`` and
``e~ = (I - B)(x - mu)`` — one masked matmul per sample, no solve
needed for the decomposition itself. Attribution of an anomalous
sample is then linear algebra, not search:

  * **which variable's mechanism broke** — the standardized noise
    scores ``z_j = e~_j / sqrt(Var e_j)``: under the fitted model each
    is ~unit-scale, so the variable whose *own* noise term is extreme
    is the root cause (its descendants look anomalous too, but their
    deviations are explained by propagation).
  * **who moved a given target** — the exact additive split
    ``x_i - mu_i = sum_j A[i, j] e~_j``: contribution of root ``j`` to
    target ``i`` is ``A[i, j] e~_j``, summing to the target's deviation
    by construction (pinned by the tests).

Everything is batched over samples (plain matmuls) and jit/vmap-clean;
:func:`attribute` is the host-facing entry, and for wide row batches it
bounds device memory by slabbing the sample axis (:data:`SAMPLE_SLAB`
rows unless the caller gives a chunk).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import api

_EPS = 1e-12
#: Default sample slab of the noise pass, in rows (shared with the query
#: engine's RCA buckets).
SAMPLE_SLAB = 512


def noise_terms_impl(adjacency, rows, mean):
    """(n, d) centered structural noise ``e~ = (I - B)(x - mu)``."""
    xc = rows.astype(jnp.float32) - mean.astype(jnp.float32)[None, :]
    return xc - xc @ adjacency.astype(jnp.float32).T


def noise_scores_impl(adjacency, rows, mean, resid_var):
    """(n, d) standardized noise scores ``e~_j / sqrt(Var e_j)``."""
    e = noise_terms_impl(adjacency, rows, mean)
    return e * jax.lax.rsqrt(jnp.maximum(resid_var, _EPS))[None, :]


def contributions_impl(adjacency, order, rows, mean, target):
    """(n, d) additive contributions of each root's noise term to the
    ``target`` variable's deviation: ``A[target, j] * e~_j`` (rows sum
    to ``x_target - mu_target``). ``target`` may be a traced index.
    Only the needed row of ``A`` is solved for (O(d^2)), so repeating
    this per sample slab costs nothing next to the slab's own matmul."""
    from .effects import target_effects_row

    t_row = target_effects_row(adjacency, order, target)
    e = noise_terms_impl(adjacency, rows, mean)
    return e * t_row[None, :]


@jax.jit
def _rca_jit(adjacency, order, rows, mean, resid_var, target):
    scores = noise_scores_impl(adjacency, rows, mean, resid_var)
    contrib = contributions_impl(adjacency, order, rows, mean, target)
    return scores, contrib


@dataclasses.dataclass
class RCAResult:
    """Attribution of a batch of (anomalous) samples."""

    scores: np.ndarray         # (n, d) standardized noise z-scores
    root: np.ndarray           # (n,) argmax |z| — the implicated variable
    target: Optional[int]      # attribution target (None = none requested)
    contributions: Optional[np.ndarray]  # (n, d) A[target, :] * e~, or None

    def ranking(self, row: int = 0, top_k: int = 5):
        """[(variable, z-score)] for one sample, by |z| descending."""
        z = self.scores[row]
        idx = np.argsort(-np.abs(z))[:top_k]
        return [(int(j), float(z[j])) for j in idx]


def _pad_rows(block: np.ndarray, slab: int, axis: int = 0) -> np.ndarray:
    """Zero-pad a slab along the sample axis to a bounded shape set.

    Full slabs pass through; short blocks (ragged tails, small
    batches) round up to the next power of two capped at ``slab`` —
    so steady-state traffic with varying row counts compiles at most
    log2(slab) + 1 program shapes instead of one per distinct length.
    Padding rows are all-zero and the per-row computations are
    independent, so real rows are bit-unchanged (callers trim).
    """
    from repro.core.batched import pow2_bucket

    k = block.shape[axis]
    target = pow2_bucket(k, slab)
    if target == k:
        return block
    pad = [(0, 0)] * block.ndim
    pad[axis] = (0, target - k)
    return np.pad(block, pad)


def attribute(
    result: api.FitResult,
    rows,
    *,
    mean=None,
    target: Optional[int] = None,
    chunk: Optional[int] = None,
) -> RCAResult:
    """Root-cause attribution of ``rows`` under a fitted graph.

    Args:
      result: the fitted graph (adjacency + order + resid_var).
      rows:   (n, d) samples to attribute (or (d,) for one).
      mean:   (d,) observational mean of the training data (None =
              centered data).
      target: optional variable index; when given, the exact additive
              contribution split toward that variable is returned too.
      chunk:  bound on the sample slab per device pass; None takes
              :data:`SAMPLE_SLAB`.
    """
    rows = np.asarray(rows, np.float32)
    if rows.ndim == 1:
        rows = rows[None, :]
    n, d = rows.shape
    mu = (
        jnp.zeros((d,), jnp.float32) if mean is None
        else jnp.asarray(mean, jnp.float32)
    )
    slab = chunk or SAMPLE_SLAB
    tgt = jnp.int32(0 if target is None else int(target))
    scores_parts, contrib_parts = [], []
    for start in range(0, n, slab):
        block = rows[start:start + slab]
        k = block.shape[0]
        s, c = _rca_jit(
            result.adjacency, result.order,
            jnp.asarray(_pad_rows(block, slab)), mu,
            jnp.asarray(result.resid_var), tgt,
        )
        scores_parts.append(np.asarray(s)[:k])
        contrib_parts.append(np.asarray(c)[:k])
    scores = np.concatenate(scores_parts, axis=0)
    contributions = (
        np.concatenate(contrib_parts, axis=0) if target is not None else None
    )
    return RCAResult(
        scores=scores,
        root=np.argmax(np.abs(scores), axis=1),
        target=target,
        contributions=contributions,
    )


def drift_root_candidates(
    adjacency,
    order,
    drift_scores,
    *,
    top_k: int = 3,
):
    """Rank candidate root variables behind a drift episode.

    ``drift_scores`` are the graph-health monitor's per-variable
    sequential-test levels (:meth:`GraphHealthMonitor.variable_scores`)
    — already expressed in the structural-noise frame, i.e. per-noise,
    with propagation through the served graph deconvolved, exactly like
    the per-sample z-scores above. But a broken *upstream* mechanism
    still leaks into descendants' residuals (their regressions were fit
    to the old mechanism), so ties are broken causally: each variable's
    own score is discounted by the strongest ancestral score, ancestors
    judged by the fitted total-effect matrix ``A = (I - B)^{-1}``.
    A variable drifting alone keeps its full score; one whose drifting
    ancestor explains it ranks below that ancestor.

    Returns ``[(variable, drift score)]``, strongest candidate first —
    the same shape as :meth:`RCAResult.ranking`.
    """
    from .effects import total_effects_impl

    z = np.abs(np.asarray(drift_scores, np.float32))
    d = z.shape[0]
    a = np.asarray(total_effects_impl(
        jnp.asarray(adjacency), jnp.asarray(order)
    ))
    reach = (np.abs(a) > _EPS) & ~np.eye(d, dtype=bool)  # [i, j]: j ancestor of i
    anc_peak = np.where(reach, z[None, :], 0.0).max(axis=1)
    adjusted = z - 0.5 * np.minimum(anc_peak, z)
    idx = np.argsort(-adjusted)[:top_k]
    return [(int(j), float(z[j])) for j in idx if z[j] > 0.0]
