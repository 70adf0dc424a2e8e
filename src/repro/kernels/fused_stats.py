"""Fused standardize + pairwise-moments Pallas kernel (§Perf C2+C3).

The baseline kernel (`pairwise_stats.py`) consumes a pre-standardized,
materialized X slab. This variant folds the standardization into the
kernel: it streams the *raw* X tiles (optionally bf16 — C3) and applies
the per-variable affine (mu, rstd) in VMEM before the residual/moment
math, so the ordering step never materializes the standardized slab in
HBM — one full slab write + read saved per ordering iteration, and the
streamed bytes halve again with bf16 input.

Correlation is NOT computed here (it comes from the raw-X MXU matmul with
the affine fold, see core/sharded.py ``fused_standardize=True``); this
kernel only needs C's rows for its i-tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .pairwise_stats import check_blocks, moment_sums


def _fused_kernel(x_i_ref, x_j_ref, mu_i_ref, mu_j_ref, rs_i_ref, rs_j_ref,
                  c_ref, m1_ref, m2_ref, **extents):
    # Standardize the raw tiles in VMEM (affine per variable row).
    def x_i(lanes):
        return (x_i_ref[:, lanes].astype(jnp.float32) - mu_i_ref[...]) \
            * rs_i_ref[...]

    def x_j(lanes):
        return (x_j_ref[:, lanes].astype(jnp.float32) - mu_j_ref[...]) \
            * rs_j_ref[...]

    moment_sums(x_i, x_j, c_ref, m1_ref, m2_ref, **extents)


@functools.partial(
    jax.jit,
    static_argnames=("m_total", "bi", "bj", "bm", "interpret"),
)
def fused_moment_sums(
    x_raw_rows,
    x_raw_all,
    mu_rows,
    mu_all,
    rstd_rows,
    rstd_all,
    c_rows,
    *,
    m_total: int,
    bi: int,
    bj: int,
    bm: int,
    interpret: bool = False,
):
    """Moment *sums* for a row tile against all variables, from raw X.

    x_raw_rows: (tile, m_pad) raw (fp32 or bf16 — §Perf C3);
    x_raw_all:  (d_pad, m_pad); mu/rstd: per-variable standardization
    constants as (tile, 1) / (d_pad, 1) columns; c_rows: (tile, d_pad)
    correlation rows. Returns (S1, S2): (tile, d_pad) fp32 sums over
    valid samples. The blocks must tile the padded shapes exactly.
    """
    tile, m_pad = x_raw_rows.shape
    d_pad = x_raw_all.shape[0]
    check_blocks(tile, d_pad, m_pad, m_total, bi, bj, bm)
    grid = (tile // bi, d_pad // bj, m_pad // bm)
    kernel = functools.partial(_fused_kernel, bm=bm, m_total=m_total)
    out_shape = [
        jax.ShapeDtypeStruct((tile, d_pad), jnp.float32),
        jax.ShapeDtypeStruct((tile, d_pad), jnp.float32),
    ]
    in_specs = [
        pl.BlockSpec((bi, bm), lambda i, j, k: (i, k)),   # raw rows
        pl.BlockSpec((bj, bm), lambda i, j, k: (j, k)),   # raw all
        pl.BlockSpec((bi, 1), lambda i, j, k: (i, 0)),    # mu rows
        pl.BlockSpec((bj, 1), lambda i, j, k: (j, 0)),    # mu all
        pl.BlockSpec((bi, 1), lambda i, j, k: (i, 0)),    # rstd rows
        pl.BlockSpec((bj, 1), lambda i, j, k: (j, 0)),    # rstd all
        pl.BlockSpec((bi, bj), lambda i, j, k: (i, j)),   # corr rows
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
        name="fused_moment_sums",
    )(x_raw_rows, x_raw_all, mu_rows, mu_all, rstd_rows, rstd_all, c_rows)
