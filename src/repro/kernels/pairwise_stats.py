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


def _chunk_sums(xi, xj, c, inv_std, limit, s1, s2):
    """Add the moment integrands of one ACCUM_CHUNK-wide sample chunk to
    the (BI, BJ) sums; with ``limit`` (traced), only its first ``limit``
    samples."""
    r = xi[:, None, :] - c[:, :, None] * xj[None, :, :]
    u = r * inv_std[:, :, None]                           # (BI, BJ, 128)
    # log cosh(u) = |u| + log1p(exp(-2|u|)) - log 2 (overflow-safe).
    au = jnp.abs(u)
    logcosh = au + jnp.log1p(jnp.exp(-2.0 * au)) - LOG2
    uexp = u * jnp.exp(-0.5 * u * u)
    if limit is not None:
        valid = jax.lax.broadcasted_iota(jnp.int32, u.shape, 2) < limit
        logcosh = jnp.where(valid, logcosh, 0.0)
        uexp = jnp.where(valid, uexp, 0.0)
    return s1 + jnp.sum(logcosh, axis=-1), s2 + jnp.sum(uexp, axis=-1)


def moment_sums(x_i, x_j, c_ref, s1_ref, s2_ref, *, bm, m_total):
    """One (BI, BJ, BM) grid cell of every moment kernel: accumulate the
    (BI, BJ) sums of ``log cosh u`` and ``u exp(-u^2/2)`` over the block's
    valid samples, one ACCUM_CHUNK-wide sub-sum at a time in sample
    order, so the fp32 reduction order is independent of ``bm``.

    ``x_i(lanes)`` gives the (BI, 128) and ``x_j(lanes)`` the (BJ, 128)
    standardized samples of the block's chunk at ``lanes``; ``c_ref``
    (BI, BJ) holds the correlations. Of the padded sample extent only the
    first ``m_total`` (static) are valid; the grid's sample axis covers
    ``ceil(m_total / bm)`` blocks."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    # Valid samples in this block: bm but in the last block, whose tail
    # chunks alone take a mask (none when m_total fills the blocks).
    k_last = (m_total - 1) // bm
    n_last = m_total - k_last * bm
    if n_last < bm:
        valid = jnp.where(k == k_last, n_last, bm)

    c = c_ref[...].astype(jnp.float32)
    # Residual of regressing x_i on x_j, standardized analytically:
    # std(r) = sqrt(1 - C^2) exactly for ddof=0-standardized columns.
    inv_std = jax.lax.rsqrt(jnp.maximum(1.0 - c * c, EPS))
    # Straight-line over the chunks: a loop over them stalls at every
    # iteration's end (13% slower at 8 chunks an iteration on a v5e),
    # so ``bm`` bounds the kernel's code and its compile time.
    sums = (s1_ref[...], s2_ref[...])
    for q in range(bm // ACCUM_CHUNK):
        lanes = pl.ds(q * ACCUM_CHUNK, ACCUM_CHUNK)
        padded = n_last < bm and (q + 1) * ACCUM_CHUNK > n_last
        limit = valid - q * ACCUM_CHUNK if padded else None
        sums = _chunk_sums(
            x_i(lanes).astype(jnp.float32),
            x_j(lanes).astype(jnp.float32),
            c, inv_std, limit, *sums,
        )
    s1_ref[...], s2_ref[...] = sums


def _kernel(x_i_ref, x_j_ref, c_ref, s1_ref, s2_ref, **extents):
    moment_sums(
        lambda lanes: x_i_ref[:, lanes], lambda lanes: x_j_ref[:, lanes],
        c_ref, s1_ref, s2_ref, **extents,
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
                      interpret, name):
    """Moment sums of the first ``rows`` rows of ``x_rows`` (rounded up to
    a row block) against all of ``x_all``: the pair-tile and row-tile
    kernels are this call on different operands."""
    tile, m_pad = x_rows.shape
    d_pad = x_all.shape[0]
    check_blocks(tile, d_pad, m_pad, m_total, bi, bj, bm)
    n_i = pl.cdiv(rows, bi)
    out = jax.ShapeDtypeStruct((n_i * bi, d_pad), jnp.float32)
    out_spec = pl.BlockSpec((bi, bj), lambda i, j, k: (i, j))
    return pl.pallas_call(
        functools.partial(_kernel, bm=bm, m_total=m_total),
        grid=(n_i, d_pad // bj, m_pad // bm),
        in_specs=[
            pl.BlockSpec((bi, bm), lambda i, j, k: (i, k)),
            pl.BlockSpec((bj, bm), lambda i, j, k: (j, k)),
            pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[out, out],
        interpret=interpret,
        name=name,
    )(x_rows, x_all, c_rows)


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
