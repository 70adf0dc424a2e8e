"""Work counts of the DirectLiNGAM ordering, kept with the benchmark.

``pair_samples`` is the algorithm's work: sequential DirectLiNGAM scores
every ordered pair of the ``d - k`` variables left at step ``k`` over all
``m`` samples, so a fit costs

    sum_k (d - k)(d - k - 1) m  =  m (d - 1) d (d + 1) / 3

pair-samples whatever schedule or kernel computes it. The per-layer rates
divide this count by measured kernel time, so dropping padded work and a
faster kernel both raise them.

``staged_padded_pair_samples`` counts what the program's staged schedule
actually hands the moment kernel: every step of a stage runs at the
stage's width, padded to the kernel's blocks. It mirrors the program's
staged schedule and its heuristic block choice (an empty tuning table) and
is printed for reference, never reported as a metric.
"""

from __future__ import annotations

SUBLANE = 8
LANE = 128


def round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def pair_samples(m: int, d: int) -> int:
    """The algorithm's pair-samples for one fit of ``m`` x ``d`` data."""
    return m * (d - 1) * d * (d + 1) // 3


def stage_schedule(d: int, frac: float = 0.25, min_stage: int = 8):
    """[(width, n_steps), ...] of staged compaction; the steps sum to d."""
    sched = []
    cur = d
    while cur > min_stage:
        n = max(1, int(round(cur * frac)))
        sched.append((cur, n))
        cur -= n
    if cur:
        sched.append((cur, cur))
    return sched


def lane_block(w: int) -> int:
    return LANE if w > LANE else round_up(max(w, 1), SUBLANE)


def padded_width(w: int) -> int:
    """Pair-column extent the kernel runs at for ``w`` live columns."""
    bj = lane_block(w)
    if bj % LANE == 0:
        return round_up(w, LANE)
    return round_up(w, SUBLANE)


def pair_tile_bm(m: int) -> int:
    if m >= 4096:
        return 2048
    return 512 if m >= 512 else 256


def staged_padded_pair_samples(m: int, d: int, frac=0.25, min_stage=8) -> int:
    """Pair-samples the whole-slab pair-tile kernel runs for one fit."""
    m_pad = round_up(m, pair_tile_bm(m))
    return sum(
        n * padded_width(w) ** 2 * m_pad
        for w, n in stage_schedule(d, frac, min_stage)
    )

