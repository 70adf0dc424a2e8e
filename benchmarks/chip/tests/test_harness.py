"""A whole run of each cell at a small size on the CPU: the harness's look
for a chip is skipped, everything else runs as on the chip. The program as
it is passes the check; the control and each fault that a cell can have
fail it."""

import copy
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
import run

CELLS = ["gene964.fit", "stocks487.varfit"]


def small(name):
    """The cell at a size the CPU runs in seconds; its check limits as
    committed."""
    bench, cell, config, traffic, limits = run.load_cell(name)
    config = copy.deepcopy(config)
    if config["name"] == "gene-964":
        config.update(m=512, d=40)
        config["generator"] = dict(config["generator"], n_interventions=8)
    else:
        config.update(m=400, d=24)
    return bench, cell, config, traffic, limits


def run_small(name, seed=2**31 + 17):
    jax.clear_caches()
    return run.run(name, seed, 0.3, False, require_tpu=False,
                   loaded=small(name))


@pytest.mark.parametrize("name", CELLS)
def test_program_as_it_is_passes(name):
    result = run_small(name)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_the_check(name):
    """The bfloat16 reference in the program's place."""
    loaded = small(name)
    limits = loaded[-1]
    for row in control.readings(name, [5, 6, 7], {5, 6, 7}, 2,
                                require_tpu=False, loaded=loaded):
        assert all(row["program"][n] <= limits[n] for n in limits), row
        assert any(row["control"][n] > limits[n] for n in limits), row


def _swap_first_and_last(impl):
    def broken(*args, **kwargs):
        order = impl(*args, **kwargs)
        return order.at[jnp.array([0, -1])].set(order[jnp.array([-1, 0])])
    return broken


def _half_batch(impl):
    def broken(x_std, c, **kwargs):
        half = x_std[: x_std.shape[0] // 2]
        return impl(half, c, **kwargs)
    return broken


def _state_unchanged(impl):
    def broken(x, active, reducer):
        _, active_new, root = impl(x, active, reducer)
        return x, active_new, root
    return broken


def _pruning_scaled(by):
    def breaker(impl):
        def broken(*args, **kwargs):
            return impl(*args, **kwargs) * by
        return broken
    return breaker


def _lag_transposed(fit):
    def broken(self, x):
        fit(self, x)
        b0 = self.adjacency_matrices_[0]
        self.adjacency_matrices_[1] = np.asarray(
            (np.eye(b0.shape[0]) - b0) @ self.var_coefs_[0].T)
        return self
    return broken


FAULTS = {
    # an answer altered where it is produced: the order's first and last
    # variables swapped as the ordering returns it
    "answer_altered": ("repro.core.ordering", "compact_order_impl",
                       _swap_first_and_last),
    # half of the batch left out: moments averaged over half the samples
    "half_batch": ("repro.kernels.ops", "pairwise_moments", _half_batch),
    # a step that returns its state unchanged: no residual update
    "state_unchanged": ("repro.core.ordering", "ordering_step",
                        _state_unchanged),
    # the pruning left out (every coefficient 0), or its answer halved
    "pruning_skipped": ("repro.core.pruning", "estimate_adjacency",
                        _pruning_scaled(0.0)),
    "pruning_halved": ("repro.core.pruning", "estimate_adjacency",
                       _pruning_scaled(0.5)),
    # the lag transform altered where it is produced: M1 transposed
    "lag_transposed": ("repro.core.var_lingam", "VarLiNGAM.fit",
                       _lag_transposed),
}
CELL_FAULTS = [(name, fault) for name in CELLS for fault in sorted(FAULTS)
               if fault != "lag_transposed" or name == "stocks487.varfit"]


@pytest.mark.parametrize("name,fault", CELL_FAULTS)
def test_fault_makes_the_run_incorrect(name, fault, monkeypatch):
    import importlib

    module_name, attr, breaker = FAULTS[fault]
    owner = importlib.import_module(module_name)
    *path, attr = attr.split(".")
    for part in path:
        owner = getattr(owner, part)
    monkeypatch.setattr(owner, attr, breaker(getattr(owner, attr)))
    result = run_small(name)
    assert not result["correct"], (fault, result["checks"])


def test_a_listed_end_to_end_metric_the_job_leaves_out_is_an_error(
        monkeypatch):
    import fitloop

    monkeypatch.setattr(fitloop.FitLoop, "end_to_end",
                        lambda self, graphs, elapsed: {})
    with pytest.raises(KeyError, match="fit_s"):
        run_small("gene964.fit")


def test_no_tpu_exits_nonzero_before_any_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), "--workload",
         "gene964.fit", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == run.EXIT_NO_DEVICE
    assert proc.stdout.strip() == ""
