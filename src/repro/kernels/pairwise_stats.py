"""Pallas TPU kernel for the LiNGAM pairwise residual-entropy moments.

This is the paper's compute hot-spot (96% of DirectLiNGAM wall-clock):
for every ordered variable pair (i, j) compute the two nonlinear moments
of the standardized regression residual

    u_ij    = (x_i - C_ij * x_j) * rsqrt(1 - C_ij^2)
    M1[i,j] = E_s[log cosh u_ij]
    M2[i,j] = E_s[u_ij * exp(-u_ij^2 / 2)]

TPU adaptation of the paper's CUDA kernel (see DESIGN.md §2):

  * The CUDA version assigns a thread block per ``i`` and threads per ``j``
    with shared-memory tree reductions over samples. On TPU we instead tile
    the (i, j) pair space into (BI, BJ) VMEM blocks and put the *sample*
    axis minor (lane dimension, 128-aligned) so the reduction is a
    vectorized VPU ``sum`` — no synchronization primitives at all.
  * The sample axis is the innermost grid dimension. TPU grid steps execute
    sequentially on a core, so the kernel accumulates partial sums in the
    output VMEM block across sample chunks — the same role the CUDA
    shared-memory accumulator plays, but with a *fixed* reduction order,
    which is why (unlike the paper's abandoned warp-tiling variant) our
    parallel results are deterministic and match the oracle.
  * X is laid out (d, m): contiguous sample vectors per variable. Blocks
    (BI, BM)/(BJ, BM) stream HBM->VMEM via BlockSpec index maps.

Grid: (ceil(d/BI), d_pad/BJ, ceil(m/BM)). All block dims are padded by the
wrapper (ops.py) to hardware-friendly multiples.

**The kernel skips the padding it can skip cheaply.** Its body is bound
by its vector work per pair-sample (three transcendentals — ``exp``,
``log1p``, ``exp`` — among it), so work is time. The pair-tile grid
covers the valid rows only (rounded up to a row block), not the padded
extent. Residuals are formed one 128-sample chunk at a time, never as a
(BI, BJ, BM) tensor. Only the chunks that can hold padding in the last
sample block take a mask; when the samples fill the blocks no mask is
emitted.

**The masked kernel** (``pairwise_moments_masked``, for interventional
data) is the same body with two static hooks: each pair's residual is
standardized by its own affine map ``u = alpha_ij (x_i - c_ij x_j) +
gamma_ij`` (the moments of the pair's common valid samples, computed
outside the kernel), and each pair-sample's terms are weighed by the
validity (0 or 1) of both variables at that sample. It streams the two
validity blocks beside the data blocks and returns sums; the caller
divides by each pair's count of common valid samples.

Block shapes come from the autotuning dispatcher
(:mod:`repro.kernels.tune`) through the ops wrappers, which pad every
extent to them; each block's last dimension is a multiple of 128 or the
whole padded extent, as the TPU requires. The sample axis accumulates
in fixed ``ACCUM_CHUNK``-wide sub-chunks, so any
``bm`` that is a multiple of it produces a bit-identical reduction order
— tuned and heuristic plans differ only in speed, never in bits.

Each ``pallas_call`` has a fixed ``name``: the custom call's instruction
name in the compiled program and in a profiler trace, whatever function
wraps it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tune.registry import ACCUM_CHUNK

EPS = 1e-12
LOG2 = 0.6931471805599453


def _chunk_sums(xi, xj, c, inv_std, limit, s1, s2, gamma=None, w=None):
    """Add the moment integrands of one ACCUM_CHUNK-wide sample chunk to
    the (BI, BJ) sums; with ``limit`` (traced), only its first ``limit``
    samples. The masked kernel adds its pairs' offsets ``gamma`` (BI, BJ)
    to ``u`` and weighs each pair-sample's terms by ``w`` (BI, BJ, 128)."""
    r = xi[:, None, :] - c[:, :, None] * xj[None, :, :]
    u = r * inv_std[:, :, None]                           # (BI, BJ, 128)
    if gamma is not None:
        u = u + gamma[:, :, None]
    # log cosh(u) = |u| + log1p(exp(-2|u|)) - log 2 (overflow-safe).
    au = jnp.abs(u)
    logcosh = au + jnp.log1p(jnp.exp(-2.0 * au)) - LOG2
    uexp = u * jnp.exp(-0.5 * u * u)
    if w is not None:
        logcosh = logcosh * w
        uexp = uexp * w
    if limit is not None:
        valid = jax.lax.broadcasted_iota(jnp.int32, u.shape, 2) < limit
        logcosh = jnp.where(valid, logcosh, 0.0)
        uexp = jnp.where(valid, uexp, 0.0)
    return s1 + jnp.sum(logcosh, axis=-1), s2 + jnp.sum(uexp, axis=-1)


def moment_sums(x_i, x_j, c_ref, s1_ref, s2_ref, *, bm, m_total,
                v_i=None, v_j=None, alpha_ref=None, gamma_ref=None):
    """One (BI, BJ, BM) grid cell of every moment kernel: accumulate the
    (BI, BJ) sums of ``log cosh u`` and ``u exp(-u^2/2)`` over the block's
    valid samples, one ACCUM_CHUNK-wide sub-sum at a time in sample
    order, so the fp32 reduction order is independent of ``bm``.

    ``x_i(lanes)`` gives the (BI, 128) and ``x_j(lanes)`` the (BJ, 128)
    standardized samples of the block's chunk at ``lanes``; ``c_ref``
    (BI, BJ) holds the correlations. Of the padded sample extent only the
    first ``m_total`` (static) are valid; the grid's sample axis covers
    ``ceil(m_total / bm)`` blocks.

    The masked kernel (``v_i``, ``v_j`` given, static) computes
    ``u = alpha (x_i - c x_j) + gamma`` with the pair's own ``alpha_ref``
    and ``gamma_ref`` (BI, BJ) in place of the analytic ``rsqrt(1 - c^2)``,
    and weighs each pair-sample by ``v_i(lanes) * v_j(lanes)``, the
    samples' validity (0 or 1). Padded samples carry weight 0, so it needs
    no sample mask of its own."""
    k = pl.program_id(2)
    masked = v_i is not None

    @pl.when(k == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    # Valid samples in this block: bm but in the last block, whose tail
    # chunks alone take a mask (none when m_total fills the blocks).
    k_last = (m_total - 1) // bm
    n_last = m_total - k_last * bm
    ragged = n_last < bm and not masked
    if ragged:
        valid = jnp.where(k == k_last, n_last, bm)

    c = c_ref[...].astype(jnp.float32)
    if masked:
        inv_std = alpha_ref[...].astype(jnp.float32)
        gamma = gamma_ref[...].astype(jnp.float32)
    else:
        # Residual of regressing x_i on x_j, standardized analytically:
        # std(r) = sqrt(1 - C^2) exactly for ddof=0-standardized columns.
        inv_std = jax.lax.rsqrt(jnp.maximum(1.0 - c * c, EPS))
        gamma = None
    # Straight-line over the chunks: a loop over them stalls at every
    # iteration's end (13% slower at 8 chunks an iteration on a v5e),
    # so ``bm`` bounds the kernel's code and its compile time.
    sums = (s1_ref[...], s2_ref[...])
    for q in range(bm // ACCUM_CHUNK):
        lanes = pl.ds(q * ACCUM_CHUNK, ACCUM_CHUNK)
        padded = ragged and (q + 1) * ACCUM_CHUNK > n_last
        limit = valid - q * ACCUM_CHUNK if padded else None
        w = None
        if masked:
            w = (v_i(lanes).astype(jnp.float32)[:, None, :]
                 * v_j(lanes).astype(jnp.float32)[None, :, :])
        sums = _chunk_sums(
            x_i(lanes).astype(jnp.float32),
            x_j(lanes).astype(jnp.float32),
            c, inv_std, limit, *sums, gamma=gamma, w=w,
        )
    s1_ref[...], s2_ref[...] = sums


def _kernel(x_i_ref, x_j_ref, c_ref, s1_ref, s2_ref, **extents):
    moment_sums(
        lambda lanes: x_i_ref[:, lanes], lambda lanes: x_j_ref[:, lanes],
        c_ref, s1_ref, s2_ref, **extents,
    )


def _masked_kernel(x_i_ref, x_j_ref, v_i_ref, v_j_ref, c_ref, alpha_ref,
                   gamma_ref, s1_ref, s2_ref, **extents):
    moment_sums(
        lambda lanes: x_i_ref[:, lanes], lambda lanes: x_j_ref[:, lanes],
        c_ref, s1_ref, s2_ref,
        v_i=lambda lanes: v_i_ref[:, lanes],
        v_j=lambda lanes: v_j_ref[:, lanes],
        alpha_ref=alpha_ref, gamma_ref=gamma_ref, **extents,
    )


def check_blocks(tile: int, d_pad: int, m_pad: int, m_total: int,
                 bi: int, bj: int, bm: int):
    """The blocks tile the padded shapes, a sample block is whole chunks,
    and the sample blocks end with the last that holds a valid sample."""
    assert tile % bi == 0 and d_pad % bj == 0 and m_pad % bm == 0, (
        tile, d_pad, m_pad, bi, bj, bm)
    assert bm % ACCUM_CHUNK == 0, bm
    assert m_pad - bm < m_total <= m_pad, (m_total, m_pad, bm)


def _moment_sums_call(x_rows, x_all, c_rows, *, rows, m_total, bi, bj, bm,
                      interpret, name, masks=None, pair_affine=None):
    """Moment sums of the first ``rows`` rows of ``x_rows`` (rounded up to
    a row block) against all of ``x_all``: the pair-tile and row-tile
    kernels are this call on different operands. With ``masks`` (the
    validity of ``x_rows`` and ``x_all``, laid out as they are) and
    ``pair_affine`` (``alpha``, ``gamma`` laid out as ``c_rows``), the
    masked kernel."""
    tile, m_pad = x_rows.shape
    d_pad = x_all.shape[0]
    check_blocks(tile, d_pad, m_pad, m_total, bi, bj, bm)
    n_i = pl.cdiv(rows, bi)
    out = jax.ShapeDtypeStruct((n_i * bi, d_pad), jnp.float32)
    out_spec = pl.BlockSpec((bi, bj), lambda i, j, k: (i, j))
    row_spec = pl.BlockSpec((bi, bm), lambda i, j, k: (i, k))
    col_spec = pl.BlockSpec((bj, bm), lambda i, j, k: (j, k))
    kernel, operands = _kernel, [x_rows, x_all, c_rows]
    in_specs = [row_spec, col_spec, out_spec]
    if masks is not None:
        kernel = _masked_kernel
        operands = [x_rows, x_all, *masks, c_rows, *pair_affine]
        in_specs = [row_spec, col_spec, row_spec, col_spec] + [out_spec] * 3
    return pl.pallas_call(
        functools.partial(kernel, bm=bm, m_total=m_total),
        grid=(n_i, d_pad // bj, m_pad // bm),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[out, out],
        interpret=interpret,
        name=name,
    )(*operands)


def pairwise_moment_sums_rows(
    x_rows,
    x_all,
    c_rows,
    *,
    m_total: int,
    bi: int,
    bj: int,
    bm: int,
    interpret: bool = False,
):
    """Row-tile variant for the sharded (shard_map) path: moment *sums*
    (not means) for rows of ``x_rows`` against all of ``x_all``.

    x_rows: (tile, m_pad); x_all: (d_pad, m_pad); c_rows: (tile, d_pad).
    Returns (S1, S2) of shape (tile, d_pad) — caller psums over sample
    shards and divides by the global sample count. The blocks must tile
    the (already padded) input shapes exactly.
    """
    return _moment_sums_call(
        x_rows, x_all, c_rows, rows=x_rows.shape[0], m_total=m_total,
        bi=bi, bj=bj, bm=bm, interpret=interpret,
        name="pairwise_moment_sums_rows",
    )


@functools.partial(
    jax.jit,
    static_argnames=("m_total", "d_total", "bi", "bj", "bm", "interpret"),
)
def pairwise_moments_pallas(
    x_t,
    c,
    *,
    m_total: int,
    d_total: int,
    bi: int,
    bj: int,
    bm: int,
    interpret: bool = False,
):
    """Pairwise residual moments via the Pallas kernel.

    Args:
      x_t: (d_pad, m_pad) standardized data, variables-major. d_pad must be
           a multiple of max(bi, bj) and m_pad a multiple of bm (the ops.py
           wrapper pads; padded samples are masked via ``m_total``).
      c:   (d_pad, d_pad) sample correlation of the *valid* region.
      m_total: number of valid samples (<= m_pad).
      d_total: number of valid variables (<= d_pad); the grid covers the
           row blocks that hold them.
    Returns:
      (M1, M2): (ceil(d_total / bi) * bi, d_pad) fp32 moment matrices
      (means over samples); entries past ``d_total`` are padding.
    """
    m1_sum, m2_sum = _moment_sums_call(
        x_t, x_t, c, rows=d_total, m_total=m_total,
        bi=bi, bj=bj, bm=bm, interpret=interpret,
        name="pairwise_moments_pallas",
    )
    inv_m = jnp.float32(1.0 / m_total)
    return m1_sum * inv_m, m2_sum * inv_m


@functools.partial(
    jax.jit,
    static_argnames=("m_total", "d_total", "bi", "bj", "bm", "interpret"),
)
def pairwise_moments_masked_pallas(
    x_t,
    v_t,
    c,
    alpha,
    gamma,
    *,
    m_total: int,
    d_total: int,
    bi: int,
    bj: int,
    bm: int,
    interpret: bool = False,
):
    """Masked pairwise residual moment *sums* via the Pallas kernel.

    Args:
      x_t:   (d_pad, m_pad) standardized data, variables-major, padded as
             for :func:`pairwise_moments_pallas`.
      v_t:   (d_pad, m_pad) validity, 1.0 where the sample counts for the
             variable, 0.0 elsewhere and in every padded sample.
      c, alpha, gamma: (d_pad, d_pad) per-pair residual map
             ``u_ij = alpha_ij (x_i - c_ij x_j) + gamma_ij``.
    Returns:
      (S1, S2): (ceil(d_total / bi) * bi, d_pad) fp32 sums over the
      samples valid for both variables of a pair; the caller divides by
      each pair's count.
    """
    return _moment_sums_call(
        x_t, x_t, c, rows=d_total, m_total=m_total,
        bi=bi, bj=bj, bm=bm, interpret=interpret,
        name="pairwise_moments_masked", masks=(v_t, v_t),
        pair_affine=(alpha, gamma),
    )
