"""Block plans of the moment kernels: a function of the shapes.

Every moment-kernel entry point in ``repro.kernels.ops`` takes its
Pallas blocks ``(bi, bj, bm)`` from the rules here, from nothing but the
shapes it sees: (m, d) for the pair tile and its masked form,
(d, m, chunk) for the row tile, (d, m) for the fused kernel. The backend
comes from the caller or from the platform (:func:`default_backend`).

**Bit-parity contract.** Block shapes only re-tile the (i, j) pair space
(per-element arithmetic untouched; pairs past the valid extents are
skipped, not computed), and the kernels accumulate the sample axis in
fixed :data:`ACCUM_CHUNK`-wide sub-chunks, in sample order, so any ``bm``
that is a multiple of ``ACCUM_CHUNK`` yields the same fp32 reduction
order. ``tests/test_kernels.py`` pins the parity over explicit blocks.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

#: Sample-axis accumulation granularity shared with the Pallas kernels
#: (``pairwise_stats`` / ``fused_stats``): any bm that is a multiple of
#: this produces a bit-identical reduction order (lane width, fp32).
ACCUM_CHUNK = 128

_SUBLANE = 8      # fp32 second-to-last-dim tile
_LANE = 128       # last-dim tile / VPU lane width
#: v5e's default scoped-VMEM limit (16 MiB) less headroom for Mosaic's
#: own scratch; see vmem_bytes().
_VMEM_BUDGET = 14 * 1024 * 1024

_BACKENDS = ("blocked", "pallas", "ref")


def _round_up(x: int, k: int) -> int:
    return ((x + k - 1) // k) * k


def vmem_bytes(bi: int, bj: int, bm: int, masked: bool = False) -> int:
    """fp32 VMEM working set of one (BI, BJ, BM) grid cell of the moment
    kernels (``pairwise_stats.moment_sums``): the double-buffered input
    blocks (x_i, x_j, correlations) and output blocks, and the residual
    and integrand tensors of the (BI, BJ, ACCUM_CHUNK) sample chunks the
    kernel forms one by one. Mosaic keeps about ten such chunk tensors
    live (compiled for v5e: 24.3 MB at (32, 128, 2048), 36.0 MB at
    (64, 128, 1024)); the model counts twelve.

    The masked kernel (``masked``) also streams the two validity blocks
    (as large as x_i's and x_j's) and two more per-pair blocks (its
    ``alpha`` and ``gamma``), and forms each chunk's weights and offset
    sum: two more chunk tensors."""
    pair_blocks = 3 if masked else 1
    sample_blocks = 2 if masked else 1
    chunk_tensors = 14 if masked else 12
    return 4 * (
        2 * (sample_blocks * (bi * bm + bj * bm)
             + pair_blocks * bi * bj)       # inputs, double-buffered
        + 2 * 2 * bi * bj                   # two outputs, double-buffered
        + chunk_tensors * bi * bj * ACCUM_CHUNK  # live chunk tensors
    )


def lane_block(d: int) -> int:
    """Column block for ``d`` pair columns: one 128-lane tile when the
    columns span more than one, else every column (padded to a sublane
    multiple) in a single block."""
    return _LANE if d > _LANE else _round_up(max(d, 1), _SUBLANE)


def padded_extent(d: int, bi: int, bj: int) -> Tuple[int, int]:
    """``(d_pad, bj)``: the padded pair-column extent and the column
    block the TPU accepts for it.

    Mosaic takes a block only when its last dimension is a multiple of
    the 128-lane tile or the whole array extent (and its second-to-last
    a multiple of 8 or the whole extent). A lane-multiple ``bj`` tiles
    the columns padded up to it and to the row block ``bi``; any other
    ``bj`` — the blocks for at most 128 columns — becomes one block
    spanning all columns, padded to a multiple of ``bi``.
    """
    if bj % _LANE == 0:
        return _round_up(d, math.lcm(bi, bj)), bj
    d_pad = _round_up(d, bi)
    return d_pad, d_pad


@functools.lru_cache(maxsize=1)
def default_interpret() -> bool:
    """Pallas interpreter only when no accelerator backs the process —
    real hardware must never silently run interpret mode."""
    import jax

    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


@functools.lru_cache(maxsize=1)
def default_backend() -> str:
    """Backend when the caller does not force one: the Pallas kernels on
    an accelerator, the blocked jnp fallback elsewhere."""
    return "pallas" if not default_interpret() else "blocked"


def resolve_backend(backend: Optional[str]) -> str:
    """``backend``, or the platform's when None; an unknown name raises
    ``ValueError``."""
    backend = backend or default_backend()
    if backend not in _BACKENDS:
        raise ValueError(
            f"unknown backend: {backend!r}; expected one of {_BACKENDS}"
        )
    return backend


def heuristic_pair_blocks(d: int, m: int) -> Tuple[int, int, int]:
    """Lane-legal pair-tile block shapes, VMEM-bounded (:func:`vmem_bytes`).

    ``bj`` is one 128-lane tile, or every column when there are at most
    128 (:func:`lane_block`): a wider column block pads the pair extent
    further at the staged widths. The kernel is straight-line code over
    the ``bm / 128`` sample chunks of a block, so ``bm`` trades grid
    steps against compile time: 4,096 ran the 4,096 x 964 kernel 5%
    faster on a v5e than 2,048, but the staged fit, which compiles one
    kernel per stage width, then takes minutes to compile. The masked
    pair tile takes the same blocks.
    """
    bi, bj = _SUBLANE, lane_block(d)
    if m >= 4096:
        bm = 2048
    elif m >= 512:
        bm = 512
    else:
        bm = 256
    return bi, bj, bm


def row_tile_blocks(d: int, m: int, chunk: Optional[int] = None
                    ) -> Tuple[int, int, int]:
    """Row-tile kernel blocks for ``m`` local samples of ``d`` columns.
    The sample block is the caller's ``chunk`` when that is lane-aligned
    and tiles ``m``, else the whole sample extent in ``ACCUM_CHUNK``
    chunks."""
    bm = (chunk if chunk and chunk % _LANE == 0 and m % chunk == 0
          else _round_up(max(m, 1), ACCUM_CHUNK))
    return _SUBLANE, lane_block(d), bm


def fused_blocks(d: int, m: int) -> Tuple[int, int, int]:
    """Fused standardize+moments kernel blocks at ``m`` samples of ``d``
    columns."""
    return _SUBLANE, lane_block(d), 512 if m >= 512 else 256
