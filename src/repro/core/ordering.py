"""Causal ordering (Algorithm 1 of the paper) — one step, three plans.

The paper parallelizes the pair loop of ``search_causal_order`` on GPU.
Here the *entire* ordering loop is a ``lax.scan`` of d identical masked
steps over a static-shape (m, d) buffer:

  step(X, active):
    1. standardize active columns (ddof=0)
    2. C = X_std^T X_std / m                        (one MXU matmul)
    3. (M1, M2) = pairwise residual moments         (Pallas kernel / jnp)
    4. entropies + MI differences -> k_list scores  (O(d^2) postprocess)
    5. root = argmax_{active} k_list                (ties -> lowest index,
                                                     matching np.argmax)
    6. residualize: x_j <- x_j - (cov(x_j, x_root)/var(x_root)) x_root

There is exactly **one** implementation of this step
(:func:`ordering_step`); what varies between execution plans is only how
the sample/pair reductions are carried out, abstracted behind a small
``Reducer`` interface:

  * :class:`LocalReducer` — plain ``jnp`` reductions on one device. This
    is both the single-device plan and the **vmap** plan: the batched
    engine (:mod:`repro.core.batched`) maps the very same step over a
    leading dataset axis.
  * :class:`MaskedReducer` — the local plan on interventional data:
    each variable's reductions run over the samples it was not
    intervened on, each pair's over the samples valid for both (the
    masked moment kernel).
  * ``MeshReducer`` (:mod:`repro.core.sharded`) — the **mesh** plan:
    samples sharded over data axes (``psum`` reductions), the (i, j)
    pair space tiled over a model axis (row-tile moments +
    ``all_gather``), run under ``shard_map``.

Inactive columns are masked out of the scores; their data still flows
through the moment computation (static shapes), which preserves the
O(d^2 m) per-step cost of the sequential algorithm while making every
step identical for XLA. Step 6 is the paper's "sequential 4%" — here it
is a vectorized rank-1 update, so the parallel fraction exceeds the
paper's 0.96.

Both scan drivers (:func:`masked_order_impl`, the full masked scan, and
:func:`compact_order_impl`, in-trace staged active-set compaction) take
any reducer, so staged compaction also runs under ``shard_map`` — stage
widths are static and padded to the reducer's ``col_multiple`` (the pair
axis size for the mesh plan) with surviving columns gathered per shard.
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ops import _round_up
from . import measures

_NEG_INF = jnp.float32(-1e30)
EPS = 1e-12


class LocalReducer:
    """Single-device reduction plan (also used, vmapped, by the batched
    engine).

    The Reducer interface every plan implements:

      * ``mean_over_samples(v) -> v.mean(axis=0)`` — the global sample
        mean (a ``psum`` of local sums on a mesh).
      * ``gram_mean(v) -> v^T v / m`` — the global Gram-matrix mean (one
        matmul here; matmul + ``psum`` on a mesh).
      * ``mask_rows(v)`` — zero rows that are sample padding (identity
        here; mesh shards carry a zero-padded tail).
      * ``moment_rows(x_std, c) -> (m1_rows, m2_rows)`` — pairwise
        residual moment *means* for this plan's row tile of the (i, j)
        pair space (the whole of it here; one model-axis tile on a mesh).
      * ``gather_rows(rows) -> (d, d)`` — assemble full moment matrices
        from the row tiles (identity here; ``all_gather`` on a mesh).
      * ``col_moments(x_std) -> (cm1, cm2)`` — per-column nonlinear
        moments for the H(x_i) entropies.
      * ``standardize(x) -> (x_std, c, mu, var)`` — delegates to the
        shared :func:`step_standardize` (a plan may override it to fuse
        the correlation into the raw-X matmul, cf.
        ``fused_standardize``). ``c`` is whatever ``moment_rows`` takes
        as its pair statistics (the masked plan: each pair's residual
        map).
      * ``residual_coef(x, xr, root, mu, var) -> (width,)`` — each
        column's regression coefficient on the root column ``xr``
        (delegates to the shared :func:`step_residual_coef`).
      * ``take_columns(idx)`` — the reducer for the columns ``idx`` of
        the working data after a compaction gather (itself here; the
        masked plan gathers its per-column validity).
      * ``col_multiple`` — physical column widths must be multiples of
        this (1 here; the pair-axis size on a mesh), honoured by the
        staged-compaction driver when it shrinks the buffer.
    """

    col_multiple = 1

    def __init__(
        self,
        backend: str = None,
        interpret: bool = None,
        moment_chunk=None,
        tune: str = "cache",
    ):
        self.backend = backend
        self.interpret = interpret
        # When set, pairwise moments accumulate over (moment_chunk, d)
        # sample slabs (ops.pairwise_moments_chunked) so the per-step
        # residual intermediate is O(chunk * d^2) regardless of m — the
        # streaming plan's rolling-window refits run with chunk-bounded
        # memory. None keeps the classic whole-slab backends.
        self.moment_chunk = moment_chunk
        # Dispatch mode for the block-shape/variant decisions
        # (repro.kernels.tune): "off" | "cache" | "auto".
        self.tune = tune

    def mean_over_samples(self, v):
        return jnp.mean(v, axis=0)

    def gram_mean(self, v):
        return (v.T @ v) / v.shape[0]

    def mask_rows(self, v):
        return v

    def standardize(self, x):
        return step_standardize(x, self)

    def moment_rows(self, x_std, c):
        if self.moment_chunk:
            return ops.pairwise_moments_chunked(
                x_std, c, chunk=self.moment_chunk,
                backend=self.backend, interpret=self.interpret,
                tune_mode=self.tune,
            )
        return ops.pairwise_moments(
            x_std, c, backend=self.backend, interpret=self.interpret,
            tune_mode=self.tune,
        )

    def gather_rows(self, rows):
        return rows

    def col_moments(self, x_std):
        return measures.nonlinear_moments(x_std, axis=0)

    def residual_coef(self, x, xr, root, mu, var):
        return step_residual_coef(x, xr, root, mu, var, self)

    def take_columns(self, idx):
        return self


class MaskedReducer(LocalReducer):
    """The local plan on interventional data: a validity mask per
    (sample, variable).

    ``valid[s, g]`` is 1.0 where sample ``s`` counts for variable ``g``
    and 0.0 where an intervention set ``g`` in that sample; ``S_g`` is the
    set of samples valid for ``g`` and ``S_ij = S_i & S_j``. Each of
    ``g``'s reductions runs over ``S_g`` (standardization, the column
    moments), each pair's over ``S_ij`` (the residual's affine
    standardization and its moments, the residual-update coefficient).
    With every sample valid this is the estimator of
    :class:`LocalReducer`.

    The reducer holds the validity of the working columns and the pair
    counts ``|S_ij|``; staged compaction gathers them with the data
    (:meth:`take_columns`). No moment chunking: each step's moments are
    one call of the masked kernel.
    """

    def __init__(self, valid, n_pair=None, *, backend=None, interpret=None,
                 tune="cache"):
        super().__init__(backend=backend, interpret=interpret, tune=tune)
        self.valid = valid
        self.count = jnp.maximum(jnp.sum(valid, axis=0), 1.0)
        if n_pair is None:
            with jax.named_scope("lingam.mask_stats"):
                n_pair = jnp.maximum(_gram(valid, valid), 1.0)
        self.n_pair = n_pair

    def take_columns(self, idx):
        return MaskedReducer(
            jnp.take(self.valid, idx, axis=1),
            self.n_pair[idx][:, idx],
            backend=self.backend, interpret=self.interpret, tune=self.tune,
        )

    def mean_over_samples(self, v):
        return jnp.sum(v * self.valid, axis=0) / self.count

    def standardize(self, x):
        """``(z, (c, alpha, gamma), mu, var)``: ``z`` standardized over
        each column's valid samples, and each pair's residual map."""
        mu = self.mean_over_samples(x)
        xc = x - mu[None, :]
        var = jnp.maximum(self.mean_over_samples(xc * xc), EPS)
        z = xc * jax.lax.rsqrt(var)[None, :]
        with jax.named_scope("lingam.mask_stats"):
            stats = masked_pair_stats(z, self.valid, self.n_pair)
        return z, stats, mu, var

    def moment_rows(self, z, stats):
        return ops.pairwise_moments_masked(
            z, self.valid, *stats, self.n_pair, backend=self.backend,
            interpret=self.interpret, tune_mode=self.tune,
        )

    def col_moments(self, z):
        logcosh, uexp = measures.nonlinear_terms(z)
        return self.mean_over_samples(logcosh), self.mean_over_samples(uexp)

    def residual_coef(self, x, xr, root, mu, var):
        """``Cov(x_j, x_r) / Var(x_r)`` over each ``S_jr``, two-pass."""
        w = self.valid * self.valid[:, root][:, None]
        n = self.n_pair[:, root]
        mean_r = jnp.sum(w * xr[:, None], axis=0) / n
        mean_j = jnp.sum(w * x, axis=0) / n
        dr = xr[:, None] - mean_r[None, :]
        cov = jnp.sum(w * (x - mean_j[None, :]) * dr, axis=0) / n
        var_r = jnp.maximum(jnp.sum(w * dr * dr, axis=0) / n, EPS)
        return cov / var_r


def _gram(a, b):
    """``a^T b`` at full fp32 precision: on a TPU the default precision
    rounds the products to bfloat16, which the pair statistics below
    cannot afford (their differences cancel)."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def masked_pair_stats(z, valid, n_pair):
    """Each pair's residual map on interventional data.

    Over ``S_ij`` (``n_pair[i, j]`` samples): ``a_i`` and ``v_i`` are the
    mean and variance of ``z_i``, ``k_ij`` the covariance of ``z_i`` and
    ``z_j``, all from the matmuls ``(V z)^T V``, ``(V z^2)^T V`` and
    ``(V z)^T (V z)`` (``V = valid``). The residual of ``z_i`` on ``z_j``,
    standardized over ``S_ij``, is

        u = ((z_i - a_i) - c (z_j - a_j)) / sqrt(v_i - k c),  c = k / v_j,

    returned as ``(c, alpha, gamma)`` with ``u = alpha (z_i - c z_j) +
    gamma``: ``alpha = (v_i - k c)^(-1/2)``, ``gamma = alpha (c a_j - a_i)``.
    """
    vz = valid * z
    s_z = _gram(vz, valid)                # [i, j] = sum over S_ij of z_i
    s_zz = _gram(vz * z, valid)
    s_zizj = _gram(vz, vz)
    a = s_z / n_pair                      # [i, j] = a_i; a.T = a_j
    var_i = jnp.maximum(s_zz / n_pair - a * a, EPS)
    k = s_zizj / n_pair - a * a.T
    c = k / var_i.T
    alpha = jax.lax.rsqrt(jnp.maximum(var_i - k * c, EPS))
    gamma = alpha * (c * a.T - a)
    return c, alpha, gamma


def step_residual_coef(x, xr, root, mu, var, reducer):
    """``Cov(x_j, x_root) / Var(x_root)`` for every working column.

    mu/var come from standardize — no extra sample reduction (on a
    mesh: no extra psum round) for the root's moments. The covariance
    is two-pass (centered product) for the same fp32-cancellation
    reason as step_standardize; pad rows are masked after centering.
    """
    mean_r = mu[root]
    var_r = var[root]
    cov = reducer.mean_over_samples(
        reducer.mask_rows((x - mu[None, :]) * (xr - mean_r)[:, None])
    )
    return cov / var_r


def step_standardize(x, reducer):
    """Shared ddof=0 standardization + correlation of the working data.

    Two-pass variance (E[(x - mu)^2], one extra reduction round per step
    on a mesh): the one-pass E[x^2] - mu^2 form catastrophically cancels
    in fp32 when column means dwarf the stds (raw prices, sensor
    offsets), which would corrupt the ordering on un-centered data.
    Padded sample rows (mesh) are re-zeroed *after* centering so they
    stay out of every downstream moment. Returns (x_std, c, mu, var) —
    the residual update reuses mu and var instead of re-reducing.
    """
    mu = reducer.mean_over_samples(x)
    xc = reducer.mask_rows(x - mu[None, :])
    var = jnp.maximum(reducer.mean_over_samples(xc * xc), EPS)
    rstd = jax.lax.rsqrt(var)
    x_std = xc * rstd[None, :]
    c = reducer.gram_mean(x_std)
    return x_std, c, mu, var


def step_scores(cm1, cm2, m1, m2, active):
    """k_list scores from the column / pairwise nonlinear moments.

    The single definition of the DirectLiNGAM score formula — every plan
    (local, vmap, mesh) feeds its reduced moments through this.
    Returns scores with -inf at inactive entries.
    """
    h_col = measures.entropy_from_moments(cm1, cm2)  # (d,)
    h_res = measures.entropy_from_moments(m1, m2)  # (d, d), [i, j]

    # diff_mi[i, j] = (H(x_j) + H(r_i<-j)) - (H(x_i) + H(r_j<-i))
    diff = (h_col[None, :] + h_res) - (h_col[:, None] + h_res.T)

    pair_ok = active[:, None] & active[None, :]
    pair_ok &= ~jnp.eye(active.shape[0], dtype=bool)
    contrib = jnp.where(pair_ok, jnp.minimum(0.0, diff) ** 2, 0.0)
    k_list = -jnp.sum(contrib, axis=1)
    return jnp.where(active, k_list, _NEG_INF)


def ordering_scores(x, active, *, backend=None, interpret=None):
    """k_list scores for one ordering step (local plan).

    Args:
      x:      (m, d) current (partially residualized) data.
      active: (d,) bool mask of variables still to be ordered.
    Returns:
      (k_list, x_std, c): scores with -inf at inactive entries; the
      standardized data and correlation (reused by the residual update).
    """
    reducer = LocalReducer(backend=backend, interpret=interpret)
    x_std, c, _, _ = reducer.standardize(x)
    m1, m2 = reducer.moment_rows(x_std, c)
    cm1, cm2 = reducer.col_moments(x_std)
    return step_scores(cm1, cm2, m1, m2, active), x_std, c


def ordering_step(x, active, reducer):
    """One masked ordering step — the shared implementation.

    Args:
      x:       (m_plan, width) working data (the plan's local sample
               rows; full columns).
      active:  (width,) bool mask of variables still to be ordered.
      reducer: the plan's Reducer (see :class:`LocalReducer`).
    Returns:
      (x_new, active_new, root): residualized data, updated mask, and
      the physical column index chosen this step.

    Each stage runs under a ``jax.named_scope`` (``lingam.standardize``,
    ``lingam.moments``, ``lingam.scores``, ``lingam.residual``): names in
    the compiled program's op metadata, which a profiler trace carries
    per device op. They add no op and leave the math unchanged.
    """
    with jax.named_scope("lingam.standardize"):
        x_std, c, mu, var = reducer.standardize(x)
    with jax.named_scope("lingam.moments"):
        rows1, rows2 = reducer.moment_rows(x_std, c)
        m1 = reducer.gather_rows(rows1)
        m2 = reducer.gather_rows(rows2)
    with jax.named_scope("lingam.scores"):
        cm1, cm2 = reducer.col_moments(x_std)
        k_list = step_scores(cm1, cm2, m1, m2, active)
        root = jnp.argmax(k_list)

    # Residualize every other active column on the root column of the
    # *unstandardized* working data (matches the sequential reference).
    with jax.named_scope("lingam.residual"):
        xr = x[:, root]
        coef = reducer.residual_coef(x, xr, root, mu, var)  # (width,)
        update = jnp.where(
            active & (jnp.arange(x.shape[1]) != root), coef, 0.0)
        x_new = x - xr[:, None] * update[None, :]
        active_new = active.at[root].set(False)

    return x_new, active_new, root


def _scan_body(reducer):
    """Shared ``lax.scan`` body: one ordering step, emits the chosen root."""

    def body(carry, _):
        xc, act = carry
        xc, act, root = ordering_step(xc, act, reducer)
        return (xc, act), root

    return body


def masked_order_impl(x, reducer, *, d=None, unroll=False):
    """Full masked scan: d identical steps at constant physical width.

    ``d`` is the number of real variables; columns at index >= d (mesh
    padding) start inactive and are never selected. Composable under
    ``jit`` / ``vmap`` / ``shard_map`` by callers building larger traced
    programs.
    """
    width = x.shape[1]
    if d is None:
        d = width
    x = x.astype(jnp.float32)
    body = _scan_body(reducer)
    init = (x, jnp.arange(width) < d)
    if unroll:
        order = []
        carry = init
        for _ in range(d):
            carry, root = body(carry, None)
            order.append(root)
        return jnp.stack(order).astype(jnp.int32)
    (_, _), order = jax.lax.scan(body, init, None, length=d)
    return order.astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("backend", "interpret", "unroll")
)
def causal_order(x, *, backend=None, interpret=None, unroll=False):
    """Full causal ordering of all d variables (local plan).

    Returns ``order`` (d,) int32 — order[p] is the variable at causal
    position p (order[0] = most exogenous).
    """
    return masked_order_impl(
        x, LocalReducer(backend=backend, interpret=interpret), unroll=unroll
    )


def _stage_schedule(d: int, frac: float = 0.25, min_stage: int = 8):
    """Static compaction schedule: [(width, n_steps), ...], sum n = d.

    Each stage runs ``n_steps`` ordering steps at logical width ``width``
    and then gathers the surviving columns into a ``width - n_steps``
    buffer. Smaller ``frac`` compacts more aggressively: total pair work
    approaches the sequential algorithm's d^3/3 instead of the masked
    scan's d^3 (frac=0.25 => ~0.43 d^3, a ~2.3x FLOP cut).
    """
    if not 0.0 < frac <= 1.0:
        raise ValueError(f"compaction frac must be in (0, 1], got {frac}")
    if min_stage < 1:
        raise ValueError(f"min_stage must be >= 1, got {min_stage}")
    sched = []
    d_cur = d
    while d_cur > min_stage:
        n = max(1, int(round(d_cur * frac)))
        sched.append((d_cur, n))
        d_cur -= n
    if d_cur:
        sched.append((d_cur, d_cur))
    return tuple(sched)


def compact_order_impl(x, reducer, *, d=None, frac=0.25, min_stage=8):
    """In-trace staged compaction: one traced program, static stage shapes.

    The whole schedule is unrolled inside a single trace — every stage
    has a static width, so the function compiles exactly once and
    composes with ``vmap`` (each batch element compacts along its *own*
    surviving columns via a batched gather) and with ``shard_map``
    (columns are replicated across sample shards, so every shard gathers
    the same survivors; widths stay multiples of
    ``reducer.col_multiple``, i.e. the pair-axis size, with freed slots
    zeroed and inactive). Active-column arithmetic is identical to the
    full masked scan — inactive columns never influence active ones — so
    the returned order matches :func:`masked_order_impl` exactly.
    """
    width = x.shape[1]
    if d is None:
        d = width
    x = x.astype(jnp.float32)
    col_multiple = reducer.col_multiple
    labels = jnp.arange(width, dtype=jnp.int32)  # current column -> original
    active = jnp.arange(width) < d
    parts = []
    for w_logical, n_steps in _stage_schedule(d, frac, min_stage):
        (x, active), roots = jax.lax.scan(
            _scan_body(reducer), (x, active), None, length=n_steps
        )
        keep = w_logical - n_steps
        with jax.named_scope("lingam.compact"):
            parts.append(labels[roots])
            if not keep:
                continue
            keep_pad = _round_up(keep, col_multiple)
            # Surviving column indices in ascending order (stable under
            # vmap: distinct keys, inactive pushed past the end).
            idx = jnp.argsort(jnp.where(active, jnp.arange(width), width))
            idx = idx[:keep_pad]
            x = jnp.take(x, idx, axis=1)
            reducer = reducer.take_columns(idx)
            labels = labels[idx]
            if keep_pad != keep:
                colmask = jnp.arange(keep_pad) < keep
                x = jnp.where(colmask[None, :], x, 0.0)
                active = colmask
            else:
                active = jnp.ones((keep,), dtype=bool)
            width = keep_pad
    with jax.named_scope("lingam.compact"):
        return jnp.concatenate(parts).astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("backend", "interpret", "frac", "min_stage"),
)
def causal_order_compact(
    x, *, backend=None, interpret=None, frac=0.25, min_stage=8
):
    """Single-compile staged-compaction ordering (see impl docstring)."""
    return compact_order_impl(
        x, LocalReducer(backend=backend, interpret=interpret),
        frac=frac, min_stage=min_stage,
    )


def causal_order_staged(
    x, *, backend=None, interpret=None, min_stage=32
):
    """Deprecated alias of :func:`causal_order_compact`.

    The original host-driven staging (one re-jit per stage) is
    superseded by the in-trace compaction, which returns the identical
    order from a single compile and composes with ``vmap`` /
    ``shard_map``. This shim remains for one release cycle.
    """
    warnings.warn(
        "causal_order_staged is deprecated; use causal_order_compact "
        "(in-trace staged compaction, single compile, identical order).",
        DeprecationWarning,
        stacklevel=2,
    )
    return causal_order_compact(
        x, backend=backend, interpret=interpret,
        min_stage=max(int(min_stage), 1),
    )
