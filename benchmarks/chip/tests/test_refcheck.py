import numpy as np
import pytest

import datagen
import refcheck


@pytest.fixture(scope="module")
def chain():
    """A causal chain: exactly one valid order, led by clear margins."""
    rng = np.random.default_rng(0)
    m, d = 3000, 12
    e = rng.uniform(0.0, 1.0, (m, d))
    x = np.zeros((m, d))
    for i in range(d):
        x[:, i] = e[:, i] + (0.8 * x[:, i - 1] if i else 0.0)
    perm = rng.permutation(d)
    return x[:, perm].astype(np.float32), np.argsort(perm)


def test_true_order_has_no_gap_and_a_wrong_one_has(chain):
    x, order = chain
    d = len(order)
    steps = list(range(d - 1))
    q, l = refcheck.whiten(x, order)
    assert max(refcheck.order_gaps(q, l, steps).values()) == 0.0
    wrong = order.copy()
    wrong[[0, d - 1]] = wrong[[d - 1, 0]]
    q, l = refcheck.whiten(x, wrong)
    assert refcheck.order_gaps(q, l, [0])[0] > 0.1


def test_adjacency_is_the_regression_on_predecessors(chain):
    x, order = chain
    b = refcheck.adjacency_from_cov(refcheck.centered_cov(x), order)
    xc = x - x.mean(axis=0)
    for pos in (1, 5, 11):
        i, pred = order[pos], order[:pos]
        coef = np.linalg.lstsq(xc[:, pred], xc[:, i], rcond=None)[0]
        np.testing.assert_allclose(b[i, pred], coef, rtol=1e-6, atol=1e-8)
        others = np.setdiff1d(np.arange(len(order)), pred)
        assert np.all(b[i, others] == 0.0)
    b_ldl = refcheck.adjacency_from_cov_ldl(refcheck.centered_cov(x), order)
    np.testing.assert_allclose(b_ldl, b, rtol=1e-9, atol=1e-12)


def test_var1_is_least_squares_with_an_intercept():
    rows = np.asarray(datagen.var_panel(
        datagen.seed_key(1), n_rows=500, d=6, edge_prob=0.4, b0_scale=0.5,
        ar_edge_prob=0.4, ar_scale=0.3)[0], np.float64)
    a, c, resid = refcheck.var1_ols(rows)
    z1 = np.concatenate([np.ones((499, 1)), rows[:-1]], axis=1)
    coef = np.linalg.lstsq(z1, rows[1:], rcond=None)[0]
    np.testing.assert_allclose(a, coef[1:].T, rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(c, coef[0], rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(resid, rows[1:] - z1 @ coef, atol=1e-9)


def test_sample_steps_cover_every_stage_start():
    steps = refcheck.sample_steps(964, np.random.default_rng(1), 14)
    assert steps[0] == 0 and 1 in steps
    assert len(steps) == len(set(steps))
    assert all(0 <= s <= 962 for s in steps)
    assert 723 - 723 + 241 in steps  # the second stage starts at step 241


def test_combine_fails_a_reading_over_its_limit_or_not_finite():
    _, ok = refcheck.combine({"a": 0.1, "b": 0.2}, {"a": 0.5, "b": 0.5})
    assert ok
    _, ok = refcheck.combine({"a": 0.6, "b": 0.2}, {"a": 0.5, "b": 0.5})
    assert not ok
    _, ok = refcheck.combine({"a": float("inf")}, {"a": 0.5})
    assert not ok
