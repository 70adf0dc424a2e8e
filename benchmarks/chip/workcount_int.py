"""Bytes the masked moment kernel (``pairwise_moments_masked``) moves
between HBM and VMEM in one interventional fit, kept with the benchmark.

The staged schedule runs, at each step of a stage of width ``w``, one
pair-tile grid of ``(ceil(w / bi), d_pad / bj, m_pad / bm)`` cells, the
sample axis innermost (``workcount`` mirrors the schedule and the
heuristic blocks). A block is fetched, or an output block written back,
when its index changes from one grid cell to the next:

* ``x_i`` and the row validity ``v_i`` ((bi, bm) each, index ``(i, k)``)
  and ``x_j``, ``v_j`` ((bj, bm), index ``(j, k)``): at every cell once
  the sample axis has two blocks or more;
* the three per-pair blocks ``c``, ``alpha``, ``gamma`` and the two
  output blocks ((bi, bj), index ``(i, j)``): once per output tile.

Every block is fp32 (4 bytes an element).
"""

from __future__ import annotations

import workcount

ROW_BLOCK = 8       # the heuristic bi
PAIR_BLOCKS = 5     # c, alpha, gamma in; the two moment sums out


def fetches(grid, axes) -> int:
    """How often a block whose index follows the grid ``axes`` changes
    index over the grid, walked in order (last axis innermost): every
    cell of the grid up to its innermost axis of more than one step."""
    inner = [a for a in axes if grid[a] > 1]
    if not inner:
        return 1
    count = 1
    for n in grid[: max(inner) + 1]:
        count *= n
    return count


def stage_grid(w: int, m: int, bi: int = ROW_BLOCK):
    """(grid, bj, bm) of the pair-tile kernel at ``w`` live columns."""
    bj = workcount.lane_block(w)
    d_pad = workcount.padded_width(w)
    bm = workcount.pair_tile_bm(m)
    m_pad = workcount.round_up(m, bm)
    return (-(-w // bi), d_pad // bj, m_pad // bm), bj, bm


def step_bytes(w: int, m: int, bi: int = ROW_BLOCK) -> int:
    """Bytes one call of the masked kernel moves at ``w`` live columns."""
    grid, bj, bm = stage_grid(w, m, bi)
    return 4 * (
        fetches(grid, (0, 2)) * 2 * bi * bm      # x_i, v_i
        + fetches(grid, (1, 2)) * 2 * bj * bm    # x_j, v_j
        + fetches(grid, (0, 1)) * PAIR_BLOCKS * bi * bj
    )


def masked_kernel_bytes(m: int, d: int, frac=0.25, min_stage=8) -> int:
    """Bytes the masked kernel moves in one staged fit of m x d data."""
    return sum(n * step_bytes(w, m)
               for w, n in workcount.stage_schedule(d, frac, min_stage))
