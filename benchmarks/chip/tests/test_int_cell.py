"""The interventional cell ``gene964int.fit`` at a small size on the CPU:
its data, its byte count, its per-layer reader, and whole runs. The
program as it is passes the check; the controls and each fault fail it."""

import copy

import jax
import numpy as np
import pytest

import control
import datagen
import datagen_int
import run
import workcount
import workcount_int

CELL = "gene964int.fit"
SEED = 2**31 + 17


def small():
    """The cell at a size the CPU runs in seconds; its check limits as
    committed."""
    bench, cell, config, traffic, limits = run.load_cell(CELL)
    config = copy.deepcopy(config)
    config.update(m=512, d=40)
    config["generator"] = dict(config["generator"], n_interventions=8)
    return bench, cell, config, traffic, limits


def run_small(seed=SEED):
    jax.clear_caches()
    return run.run(CELL, seed, 0.3, False, require_tpu=False, loaded=small())


GEN = dict(edge_prob=0.2, weight=0.5, n_interventions=6,
           intervention_share=0.8, do_value=5.0)


@pytest.mark.parametrize("seed", [3, 2**33 + 7])
def test_rows_are_the_pooled_cells_and_the_mask_marks_the_knockouts(seed):
    key = datagen.seed_key(seed)
    x, intervened = datagen_int.gene_dataset_int(key, m=300, d=24, **GEN)
    x_pooled = datagen.gene_dataset(key, m=300, d=24, **GEN)[0]
    x, intervened = np.asarray(x), np.asarray(intervened)
    assert np.array_equal(x, np.asarray(x_pooled))
    # One knockout in each of the first 80% of the rows, none after.
    assert intervened.sum(axis=1).tolist() == [1] * 240 + [0] * 60
    # The generator sets a knocked-out value as x + (do_value - x) in
    # float32, which lands on do_value or one ulp beside it.
    ulp = np.spacing(np.float32(GEN["do_value"]))
    assert (np.abs(x[intervened] - GEN["do_value"]) <= ulp).all()
    # Only the knocked-out genes' columns are ever masked.
    assert intervened.any(axis=0).sum() <= GEN["n_interventions"]


def test_byte_count_by_hand():
    """m = 512 (one 512-sample block), d = 12: the stages (12, 3), (9, 2),
    (7, 7) run one column block of 16, 16 and 8 columns; 2, 2 and 1 row
    blocks of 8. With one sample block and one column block, x_j and v_j
    are fetched once per call; x_i and v_i once per row block, as are the
    five (8, bj) pair blocks."""
    bi, bm = 8, 512
    by_hand = 4 * (
        3 * (2 * 2 * bi * bm + 2 * 16 * bm + 2 * 5 * bi * 16)
        + 2 * (2 * 2 * bi * bm + 2 * 16 * bm + 2 * 5 * bi * 16)
        + 7 * (1 * 2 * bi * bm + 2 * 8 * bm + 1 * 5 * bi * 8))
    assert workcount_int.masked_kernel_bytes(512, 12) == by_hand


def _walk(grid, axes):
    """Index changes of a block over the grid, walked cell by cell."""
    count, last = 0, None
    for i in range(grid[0]):
        for j in range(grid[1]):
            for k in range(grid[2]):
                idx = tuple((i, j, k)[a] for a in axes)
                count += idx != last
                last = idx
    return count


@pytest.mark.parametrize("grid", [(3, 2, 2), (5, 1, 1), (4, 3, 1),
                                  (1, 1, 3), (2, 1, 2)])
@pytest.mark.parametrize("axes", [(0, 2), (1, 2), (0, 1)])
def test_fetch_count_walks_the_grid(grid, axes):
    assert workcount_int.fetches(grid, axes) == _walk(grid, axes)


def test_stage_grid_follows_the_schedule_and_blocks():
    for w, _ in workcount.stage_schedule(964):
        grid, bj, bm = workcount_int.stage_grid(w, 4096)
        assert grid[0] == -(-w // 8) and bm == 2048 and grid[2] == 2
        assert grid[1] * bj == workcount.padded_width(w)


def _reader():
    import os

    path = os.path.join(run.HERE, "metrics",
                        "masked_moment_hbm_roofline_pct.int.py")
    return run.load_module(path, "metric_roofline_int")


def test_roofline_reader():
    reader = _reader()
    work = {"device_kind": "TPU v5 lite", "masked_moment_bytes": 819e9}
    reduced = {"graphs": 2, "device_ops": [
        ["pallas:pairwise_moments_masked", 8.0], ["fusion", 1.0]]}
    # 1 s at the peak against 4 s a graph.
    assert reader.read(reduced, work) == pytest.approx(25.0)
    # A trace without the masked kernel (the pooled program) reads nothing.
    assert reader.read({"graphs": 2, "device_ops": [
        ["pallas:pairwise_moments_pallas", 8.0]]}, work) is None
    with pytest.raises(KeyError, match="no HBM bandwidth"):
        reader.read(reduced, dict(work, device_kind="TPU v9"))


def test_program_as_it_is_passes():
    result = run_small()
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["checks"]) == {"order_gap", "adjacency_err",
                                     "intervened_adj_err"}


def test_controls_fail_the_check():
    """The bfloat16 reference in the program's place, and the mask ignored
    (the pooled program's fit of the same rows): each fails a compared
    number on every seed."""
    loaded = small()
    limits = loaded[-1]
    for row in control.readings(CELL, [5, 6, 7], {5, 6, 7}, 1,
                                require_tpu=False, loaded=loaded):
        assert all(row["program"][n] <= limits[n] for n in limits), row
        assert any(row["control"][n] > limits[n] for n in limits), row
        pooled = {n: row["control"]["pooled." + n] for n in limits}
        assert any(pooled[n] > limits[n] for n in limits), row


def _mask_ignored(fit_fn):
    def broken(x, config=None, intervened=None):
        return fit_fn(x, config)
    return broken


def _half_batch(impl):
    def broken(x_std, valid, *args, **kwargs):
        half = x_std.shape[0] // 2
        return impl(x_std[:half], valid[:half], *args, **kwargs)
    return broken


def _pruning_skipped(impl):
    def broken(*args, **kwargs):
        return impl(*args, **kwargs) * 0.0
    return broken


FAULTS = {
    # the mask dropped on its way into the program: the pooled fit
    "mask_ignored": ("repro.core.api", "fit_fn", _mask_ignored),
    # moments averaged over half the samples
    "half_batch": ("repro.kernels.ops", "pairwise_moments_masked",
                   _half_batch),
    # the pruning left out: every coefficient 0
    "pruning_skipped": ("repro.core.pruning", "estimate_adjacency",
                        _pruning_skipped),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(fault, monkeypatch):
    import importlib

    module_name, attr, breaker = FAULTS[fault]
    owner = importlib.import_module(module_name)
    monkeypatch.setattr(owner, attr, breaker(getattr(owner, attr)))
    result = run_small()
    assert not result["correct"], (fault, result["checks"])


def test_parent_program_fails_at_setup(monkeypatch):
    """A program whose facade takes no ``intervened`` (the one before
    this cell) stops at set-up with a TypeError, not a hang."""
    from repro.core import DirectLiNGAM

    def fit(self, x):
        raise AssertionError("not reached")

    monkeypatch.setattr(DirectLiNGAM, "fit", fit)
    with pytest.raises(TypeError, match="intervened"):
        run_small()

