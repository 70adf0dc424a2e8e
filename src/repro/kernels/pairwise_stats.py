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

Grid: (d/BI, d/BJ, ceil(m/BM)). All block dims are padded by the wrapper
(ops.py) to hardware-friendly multiples; padding samples are masked here.

Block shapes come from the autotuning dispatcher
(:mod:`repro.kernels.tune`) through the ops wrappers, which pad every
extent to them; each block's last dimension is a multiple of 128 or the
whole padded extent, as the TPU requires. The sample axis accumulates
in fixed ``ACCUM_CHUNK``-wide sub-chunks, so any
``bm`` that is a multiple of it produces a bit-identical reduction order
— tuned and heuristic plans differ only in speed, never in bits (the
zero-masked padded tail contributes exact ``+0.0`` terms).

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


def _accumulate(m1_ref, m2_ref, logcosh, uexp, bm):
    """Accumulate the (BI, BJ, BM) moment integrands into the output
    block in fixed ACCUM_CHUNK-wide sample sub-sums, so the fp32
    reduction order is independent of the ``bm`` block choice."""
    if bm > ACCUM_CHUNK and bm % ACCUM_CHUNK == 0:
        a1 = m1_ref[...]
        a2 = m2_ref[...]
        for s in range(bm // ACCUM_CHUNK):
            sl = slice(s * ACCUM_CHUNK, (s + 1) * ACCUM_CHUNK)
            a1 = a1 + jnp.sum(logcosh[..., sl], axis=-1)
            a2 = a2 + jnp.sum(uexp[..., sl], axis=-1)
        m1_ref[...] = a1
        m2_ref[...] = a2
    else:
        m1_ref[...] += jnp.sum(logcosh, axis=-1)
        m2_ref[...] += jnp.sum(uexp, axis=-1)


def _kernel(x_i_ref, x_j_ref, c_ref, m1_ref, m2_ref, *, bm, m_total):
    """One (BI, BJ, BM) grid cell: accumulate moment partial sums."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        m1_ref[...] = jnp.zeros_like(m1_ref)
        m2_ref[...] = jnp.zeros_like(m2_ref)

    xi = x_i_ref[...].astype(jnp.float32)  # (BI, BM)
    xj = x_j_ref[...].astype(jnp.float32)  # (BJ, BM)
    c = c_ref[...].astype(jnp.float32)     # (BI, BJ)

    # Mask samples that fall into the zero-padded tail of the last chunk.
    sample_ids = k * bm + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bm), 2)
    valid = sample_ids < m_total  # (1, 1, BM)

    # Residual of regressing x_i on x_j, standardized analytically:
    # std(r) = sqrt(1 - C^2) exactly for ddof=0-standardized columns.
    inv_std = jax.lax.rsqrt(jnp.maximum(1.0 - c * c, EPS))  # (BI, BJ)
    r = xi[:, None, :] - c[:, :, None] * xj[None, :, :]     # (BI, BJ, BM)
    u = r * inv_std[:, :, None]
    u = jnp.where(valid, u, 0.0)

    # log cosh(u) = |u| + log1p(exp(-2|u|)) - log 2  (overflow-safe).
    au = jnp.abs(u)
    logcosh = au + jnp.log1p(jnp.exp(-2.0 * au)) - LOG2
    logcosh = jnp.where(valid, logcosh, 0.0)
    uexp = u * jnp.exp(-0.5 * u * u)  # already 0 where masked

    _accumulate(m1_ref, m2_ref, logcosh, uexp, bm)


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
    tile, m_pad = x_rows.shape
    d_pad = x_all.shape[0]
    assert tile % bi == 0 and d_pad % bj == 0 and m_pad % bm == 0, (
        tile, d_pad, m_pad, bi, bj, bm)
    grid = (tile // bi, d_pad // bj, m_pad // bm)
    kernel = functools.partial(_kernel, bm=bm, m_total=m_total)
    out_shape = [
        jax.ShapeDtypeStruct((tile, d_pad), jnp.float32),
        jax.ShapeDtypeStruct((tile, d_pad), jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec((bi, bm), lambda i, j, k: (i, k)),
        pl.BlockSpec((bj, bm), lambda i, j, k: (j, k)),
        pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
    ]
    out_specs = [
        pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
        pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
    ]
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="pairwise_moment_sums_rows",
    )(x_rows, x_all, c_rows)


@functools.partial(
    jax.jit, static_argnames=("m_total", "bi", "bj", "bm", "interpret")
)
def pairwise_moments_pallas(
    x_t,
    c,
    *,
    m_total: int,
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
    Returns:
      (M1, M2): (d_pad, d_pad) fp32 moment matrices (means over samples).
    """
    d_pad, m_pad = x_t.shape
    assert d_pad % bi == 0 and d_pad % bj == 0, (d_pad, bi, bj)
    assert m_pad % bm == 0, (m_pad, bm)
    grid = (d_pad // bi, d_pad // bj, m_pad // bm)

    kernel = functools.partial(_kernel, bm=bm, m_total=m_total)
    out_shape = [
        jax.ShapeDtypeStruct((d_pad, d_pad), jnp.float32),
        jax.ShapeDtypeStruct((d_pad, d_pad), jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec((bi, bm), lambda i, j, k: (i, k)),
        pl.BlockSpec((bj, bm), lambda i, j, k: (j, k)),
        pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
    ]
    out_specs = [
        pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
        pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),
    ]
    m1_sum, m2_sum = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="pairwise_moments_pallas",
    )(x_t, x_t, c)
    inv_m = jnp.float32(1.0 / m_total)
    return m1_sum * inv_m, m2_sum * inv_m
