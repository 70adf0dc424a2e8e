"""The interventional DirectLiNGAM fit (``fit_fn(x, config, intervened=M)``)
against its plain reference, ``baselines/interventional_lingam.py``, and
the masked moment kernel against the masked jnp oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines import interventional_lingam, sequential_lingam
from repro.core import DirectLiNGAM, api, batched, ordering
from repro.kernels import ops, ref


def knockout_data(seed, m=400, d=14, n_targets=4, share=0.5, do_value=5.0,
                  edge_prob=0.3):
    """A sparse LiNGAM SEM (Laplace noise, each earlier variable a parent
    with probability ``edge_prob``) with single-variable knockouts:
    ``share`` of the samples carry one ``do(x_g = do_value)`` on one of the
    first ``n_targets`` variables of the causal order. Returns
    ``(x, intervened)``, columns permuted off the causal order."""
    rng = np.random.default_rng(seed)
    b = np.tril(rng.normal(size=(d, d)) * (rng.random((d, d)) < edge_prob),
                -1)
    e = rng.laplace(size=(m, d))
    target = np.where(rng.random(m) < share,
                      rng.integers(0, n_targets, m), -1)
    x = np.zeros((m, d))
    for g in range(d):
        x[:, g] = x @ b[g] + e[:, g]
        x[target == g, g] = do_value
    intervened = target[:, None] == np.arange(d)[None, :]
    perm = rng.permutation(d)
    return x[:, perm].astype(np.float32), intervened[:, perm]


# fp32 (the program) against float64 (the reference): covariances of data
# whose knocked-out values sit 5 from the mean, solved for up to d - 1
# predecessors, agree to about 1e-6 of the largest entry; 1e-4 leaves room
# for the conditioning of the small predecessor systems.
ADJ_RTOL = 1e-4


def _rel_err(got, want):
    return np.abs(np.asarray(got) - want).max() / np.abs(want).max()


@pytest.mark.parametrize("seed,compaction", [
    (0, "staged"), (1, "none"), (2, "staged"),
])
def test_fit_matches_interventional_reference(seed, compaction):
    x, intervened = knockout_data(seed)
    order_ref, b_ref = interventional_lingam.fit(x, intervened)
    res = api.fit_fn(jnp.asarray(x), api.FitConfig(compaction=compaction),
                     intervened=intervened)
    np.testing.assert_array_equal(np.asarray(res.order), order_ref)
    assert _rel_err(res.adjacency, b_ref) < ADJ_RTOL
    # The mask matters here: the pooled fit answers differently.
    pooled = sequential_lingam.ols_adjacency_sequential(x, order_ref)
    assert _rel_err(pooled, b_ref) > 100 * ADJ_RTOL


def test_facade_pallas_kernel_matches_reference():
    """The facade's staged fit through the masked Pallas kernel
    (interpret mode on the CPU), and its residual variances over each
    variable's valid samples."""
    x, intervened = knockout_data(3, m=256, d=12)
    order_ref, b_ref = interventional_lingam.fit(x, intervened)
    model = DirectLiNGAM(backend="pallas", compaction="staged").fit(
        x, intervened=intervened)
    np.testing.assert_array_equal(model.causal_order_, order_ref)
    assert _rel_err(model.adjacency_, b_ref) < ADJ_RTOL
    x64 = x.astype(np.float64)
    resid = (x64 - x64.mean(0)) - (x64 - x64.mean(0)) @ b_ref.T
    want = [resid[~intervened[:, j], j].var() for j in range(x.shape[1])]
    np.testing.assert_allclose(model.resid_var_, want, rtol=1e-4)


def test_all_false_mask_agrees_with_unmasked_fit():
    x, _ = knockout_data(4)
    cfg = api.FitConfig(compaction="staged")
    plain = api.fit_fn(jnp.asarray(x), cfg)
    masked = api.fit_fn(jnp.asarray(x), cfg,
                        intervened=np.zeros(x.shape, bool))
    np.testing.assert_array_equal(np.asarray(masked.order),
                                  np.asarray(plain.order))
    # Both are fp32: the masked pruning forms each row's covariance in one
    # pass over a centered copy, the plain one shares a two-pass
    # covariance, so they agree to rounding, as amplified by the
    # predecessor systems (ADJ_RTOL's reason).
    assert _rel_err(masked.adjacency, np.asarray(plain.adjacency)) < ADJ_RTOL
    np.testing.assert_allclose(np.asarray(masked.resid_var),
                               np.asarray(plain.resid_var), rtol=ADJ_RTOL)


def _masked_inputs(m, d, seed=0):
    # About three parents a variable: denser graphs at d = 129 make pairs
    # collinear to 1e-6, whose residuals no fp32 computation resolves.
    x, intervened = knockout_data(seed, m=m, d=d, n_targets=min(d, 6),
                                  share=0.6, edge_prob=min(0.3, 3.0 / d))
    valid = jnp.asarray(~intervened, jnp.float32)
    reducer = ordering.MaskedReducer(valid)
    z, stats, _, _ = reducer.standardize(jnp.asarray(x))
    return z, valid, stats, reducer.n_pair


# m = 300 fills no sample block; d = 129 spans two 128-lane column blocks;
# m = 64 and 200 leave the one 256-sample block less than a 128-sample
# chunk, or a chunk and a part, of samples.
SHAPES = [(300, 13), (512, 24), (256, 129), (64, 13), (200, 13)]


def _offdiag_close(got, want, atol):
    off = 1.0 - np.eye(got[0].shape[0])
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g) * off, np.asarray(w) * off,
                                   atol=atol, rtol=0)


@pytest.mark.parametrize("m,d", SHAPES)
def test_masked_pallas_kernel_matches_jnp(m, d):
    """The kernel (interpret mode) against the masked jnp moments on the
    same pair statistics: the same arithmetic, summed in another order."""
    z, valid, stats, n_pair = _masked_inputs(m, d)
    want = ops.pairwise_moments_masked(z, valid, *stats, n_pair,
                                       backend="blocked")
    got = ops.pairwise_moments_masked(z, valid, *stats, n_pair,
                                      backend="pallas", interpret=True)
    _offdiag_close(got, want, atol=2e-6)


@pytest.mark.parametrize("m,d", SHAPES)
def test_masked_moments_match_their_definition(m, d):
    """Pair statistics and kernel against the oracle that forms every
    pair's moments two-pass on its own samples. The step's statistics are
    one-pass matmul sums; for a strongly correlated pair the residual's
    variance ``v_i - k c`` cancels and scales their fp32 rounding up, so
    the tolerance is 1e-5 (the moments are O(1))."""
    z, valid, stats, n_pair = _masked_inputs(m, d)
    want = ref.pairwise_moments_masked_ref(z, valid)
    got = ops.pairwise_moments_masked(z, valid, *stats, n_pair,
                                      backend="pallas", interpret=True)
    _offdiag_close(got, want, atol=1e-5)


def test_pair_without_a_common_sample_keeps_zero_moments():
    """The kernel's first sum carries log 2 a weighed sample, which the
    wrapper takes off each pair's mean; a pair that shares no valid
    sample has no mean, and keeps the oracle's 0 (its count clamps to 1)."""
    z, valid, _, _ = _masked_inputs(300, 13)
    half = np.arange(300) < 150
    valid = valid.at[:, 0].set(jnp.where(half, valid[:, 0], 0.0))
    valid = valid.at[:, 1].set(jnp.where(half, 0.0, valid[:, 1]))
    reducer = ordering.MaskedReducer(valid)
    zm, stats, _, _ = reducer.standardize(z)
    assert float(reducer.n_pair[0, 1]) == 1.0
    got = ops.pairwise_moments_masked(zm, valid, *stats, reducer.n_pair,
                                      backend="pallas", interpret=True)
    want = ref.pairwise_moments_masked_ref(zm, valid)
    for g in got:
        assert float(g[0, 1]) == 0.0 and float(g[1, 0]) == 0.0
    _offdiag_close(got, want, atol=1e-5)


def test_every_sample_valid_gives_the_unmasked_moments():
    x, _ = knockout_data(5, m=300, d=13)
    z = ops.standardize(jnp.asarray(x))
    valid = jnp.ones(x.shape, jnp.float32)
    reducer = ordering.MaskedReducer(valid)
    zm, stats, _, _ = reducer.standardize(jnp.asarray(x))
    got = ops.pairwise_moments_masked(zm, valid, *stats, reducer.n_pair,
                                      backend="pallas", interpret=True)
    want = ops.pairwise_moments(z, ops.correlation(z), backend="pallas",
                                interpret=True)
    _offdiag_close(got, want, atol=2e-6)


def _mesh_fit(x, m):
    cfg = api.FitConfig(partition=api.Partition(mesh=(("data", 1),
                                                      ("model", 1))))
    return api.fit_fn(x, cfg, intervened=m)


def _vmap_fit(x, m):
    return batched.fit_many(x[None], api.FitConfig(), intervened=m[None])


def _from_stats_fit(x, m):
    xc = x - x.mean(0)
    return api.fit_from_stats(x, x.mean(0), xc.T @ xc / x.shape[0],
                              api.FitConfig(), intervened=m)


def _chunked_fit(x, m):
    return api.fit_fn(x, api.FitConfig(moment_chunk=64), intervened=m)


def _lasso_fit(x, m):
    return api.fit_fn(x, api.FitConfig(prune_method="adaptive_lasso"),
                      intervened=m)


def _wrong_shape(x, m):
    return api.fit_fn(x, api.FitConfig(), intervened=m[:, 1:])


@pytest.mark.parametrize("fit", [_mesh_fit, _vmap_fit, _from_stats_fit,
                                 _chunked_fit, _lasso_fit, _wrong_shape])
def test_plans_without_an_interventional_fit_reject_a_mask(fit):
    x, intervened = knockout_data(6, m=64, d=6)
    with pytest.raises(ValueError):
        fit(x, intervened)
