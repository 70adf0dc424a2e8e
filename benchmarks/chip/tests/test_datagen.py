import numpy as np
import pytest

import datagen


def is_topological(b, order):
    """B in ``order`` is strictly lower-triangular: no variable has a
    parent later in the order."""
    b_o = np.asarray(b)[np.ix_(order, order)]
    return np.all(np.triu(b_o) == 0.0)


def test_seed_key_takes_seeds_past_32_bits():
    import jax

    a = jax.random.key_data(datagen.seed_key(2**33 + 5))
    b = jax.random.key_data(datagen.seed_key(5))
    c = jax.random.key_data(datagen.seed_key(2**33 + 5))
    assert not np.array_equal(a, b)
    assert np.array_equal(a, c)


def test_gene_dataset_shapes_order_and_interventions():
    x, b, order = datagen.gene_dataset(
        datagen.seed_key(3), m=400, d=30, edge_prob=0.2, weight=0.5,
        n_interventions=5, intervention_share=0.5, do_value=5.0)
    x, b, order = map(np.asarray, (x, b, order))
    assert x.shape == (400, 30) and b.shape == (30, 30)
    assert np.isfinite(x).all()
    assert sorted(order.tolist()) == list(range(30))
    assert is_topological(b, order)
    assert np.any(b != 0)
    # Half the rows hold one gene at the do-value.
    pinned = np.isclose(x, 5.0, atol=1e-4).any(axis=1)
    assert pinned[:200].all()


def test_gene_dataset_is_a_function_of_the_seed():
    kw = dict(m=64, d=10, edge_prob=0.3, weight=0.5, n_interventions=3,
              intervention_share=0.8, do_value=5.0)
    a = np.asarray(datagen.gene_dataset(datagen.seed_key(9), **kw)[0])
    b = np.asarray(datagen.gene_dataset(datagen.seed_key(9), **kw)[0])
    c = np.asarray(datagen.gene_dataset(datagen.seed_key(10), **kw)[0])
    assert np.array_equal(a, b) and not np.array_equal(a, c)


@pytest.mark.parametrize("ar_scale", [0.2, 3.0])
def test_var_panel_is_stationary_and_ordered(ar_scale):
    x, b0, m1, order = map(np.asarray, datagen.var_panel(
        datagen.seed_key(4), n_rows=3000, d=12, edge_prob=0.3,
        b0_scale=0.5, ar_edge_prob=0.3, ar_scale=ar_scale))
    assert x.shape == (3000, 12)
    assert is_topological(b0, order)
    a = np.linalg.solve(np.eye(12) - b0, m1)
    assert np.abs(np.linalg.eigvals(a)).max() < 0.951
    # A stationary series: the second half's spread is like the first's.
    s1, s2 = x[500:1750].std(axis=0), x[1750:].std(axis=0)
    assert np.all(s2 < 2.0 * s1) and np.all(s1 < 2.0 * s2)
    assert np.isfinite(x).all()
