import pytest

import workcount
from repro.core import ordering
from repro.kernels.tune import registry


def brute_pair_samples(m, d):
    active = d
    total = 0
    for _ in range(d):
        total += sum(1 for i in range(active) for j in range(active) if i != j)
        active -= 1
    return total * m


@pytest.mark.parametrize("d", [1, 2, 3, 9, 40, 131])
def test_pair_samples_counts_every_ordered_pair_of_every_step(d):
    assert workcount.pair_samples(7, d) == brute_pair_samples(7, d)


@pytest.mark.parametrize("d", [5, 8, 9, 64, 127, 129, 487, 964])
def test_stage_schedule_mirrors_the_program(d):
    assert tuple(workcount.stage_schedule(d)) == ordering._stage_schedule(d)


@pytest.mark.parametrize("w", [1, 7, 8, 100, 127, 128, 129, 487, 964])
def test_padded_width_mirrors_the_kernel_blocks(w):
    bj = registry.lane_block(w)
    assert workcount.padded_width(w) == registry.padded_extent(w, 8, bj)[0]


@pytest.mark.parametrize("m,d", [(512, 20), (4096, 964), (1000, 130)])
def test_staged_padded_count_by_brute_force(m, d):
    """Walk the steps one by one: each runs at its stage's padded width."""
    total = 0
    width = d
    for w, n in ordering._stage_schedule(d):
        assert w == width
        for _ in range(n):
            pad = registry.padded_extent(w, 8, registry.lane_block(w))[0]
            bm = registry.heuristic_pair_blocks(w, m)[2]
            total += pad * pad * (-(-m // bm) * bm)
        width -= n
    assert workcount.staged_padded_pair_samples(m, d) == total
    assert total >= workcount.pair_samples(m, d)

