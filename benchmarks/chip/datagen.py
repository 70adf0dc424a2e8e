"""Seeded data on the device: the benchmark's inputs, made from ``--seed``.

Both generators follow the program's own simulators (``data/simulate.py``)
but run as one jitted call each on the device instead of Python loops on
the host:

* :func:`gene_dataset` -- ``simulate_gene_perturb``: a sparse LiNGAM SEM
  over ``d`` genes (each earlier gene a parent with probability
  ``edge_prob``, effects ``weight * N(0, 1)``, Laplace noise), with
  single-gene interventions ``do(x_g = do_value)`` pooled into the rows.
  Gene identities are permuted so that the true order is not the index
  order.
* :func:`var_panel` -- ``simulate_var_stocks``: a stationary VAR(1)
  ``x_t = B0 x_t + M1 x_{t-1} + e_t`` with a sparse LiNGAM ``B0``, as a
  ``lax.scan``. Stationarity is guarded by the spectral norm of
  ``(I - B0)^-1 M1``, which bounds its spectral radius (the host simulator
  uses eigenvalues, which the TPU does not compute).

Every matrix product here runs at ``highest`` precision: the data are the
yardstick, not the system under test.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, including seeds past 2**32."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    high = seed >> 31
    while high:
        key = jax.random.fold_in(key, high & 0x7FFFFFFF)
        high >>= 31
    return key


def _sparse_lower(key, d, edge_prob, scale):
    k_mask, k_w = jax.random.split(key)
    lower = jnp.tril(jnp.ones((d, d), bool), k=-1)
    mask = lower & (jax.random.uniform(k_mask, (d, d)) < edge_prob)
    return jnp.where(mask, jax.random.normal(k_w, (d, d)) * scale, 0.0)


def _unit_lower_inverse(b):
    """(I - B)^-1 for strictly lower-triangular B."""
    d = b.shape[0]
    eye = jnp.eye(d, dtype=jnp.float32)
    return jax.scipy.linalg.solve_triangular(eye - b, eye, lower=True)


@functools.partial(
    jax.jit,
    static_argnames=("m", "d", "edge_prob", "weight", "n_interventions",
                     "intervention_share", "do_value"),
)
def gene_dataset(key, *, m, d, edge_prob, weight, n_interventions,
                 intervention_share, do_value):
    """(x, b, order): (m, d) f32 rows, the true (d, d) adjacency
    (``b[i, j]`` = effect of gene j on gene i) and a true causal order."""
    k_b, k_e, k_t, k_p = jax.random.split(key, 4)
    b = _sparse_lower(k_b, d, edge_prob, weight)
    t = _unit_lower_inverse(b)  # x = T e
    e = jax.random.laplace(k_e, (m, d), jnp.float32)
    x = jnp.dot(e, t.T, precision=HIGHEST)
    # do(x_g = v) on a row: x + (v - x_g) T[:, g], since T[g, g] = 1 and
    # every other structural equation still holds.
    n_int_rows = int(intervention_share * m)
    target = jnp.where(
        jnp.arange(m) < n_int_rows,
        jax.random.randint(k_t, (m,), 0, n_interventions),
        -1,
    )
    g = jnp.maximum(target, 0)
    shift = jnp.where(target >= 0, do_value - x[jnp.arange(m), g], 0.0)
    x = x + shift[:, None] * t.T[g]
    perm = jax.random.permutation(k_p, d)
    order = jnp.argsort(perm).astype(jnp.int32)
    return x[:, perm], b[perm][:, perm], order


@functools.partial(
    jax.jit,
    static_argnames=("n_rows", "d", "edge_prob", "b0_scale", "ar_edge_prob",
                     "ar_scale"),
)
def var_panel(key, *, n_rows, d, edge_prob, b0_scale, ar_edge_prob, ar_scale):
    """(x, b0, m1, order): an (n_rows, d) f32 VAR(1) panel, its
    instantaneous and lag-1 matrices, and a true order of ``b0``."""
    k_b, k_m, k_mm, k_e, k_p = jax.random.split(key, 5)
    b0 = _sparse_lower(k_b, d, edge_prob, b0_scale)
    m1 = (jax.random.normal(k_m, (d, d))
          * (jax.random.uniform(k_mm, (d, d)) < ar_edge_prob) * ar_scale)
    t = _unit_lower_inverse(b0)
    a = jnp.dot(t, m1, precision=HIGHEST)
    norm = jnp.linalg.norm(a, 2)
    shrink = jnp.where(norm >= 0.95, 0.9 / norm, 1.0)
    m1 = m1 * shrink
    a = a * shrink
    e = jax.random.laplace(k_e, (n_rows, d), jnp.float32)
    te = jnp.dot(e, t.T, precision=HIGHEST)

    def step(prev, te_t):
        cur = jnp.dot(a, prev, precision=HIGHEST) + te_t
        return cur, cur

    _, rest = jax.lax.scan(step, jnp.zeros((d,), jnp.float32), te[1:])
    x = jnp.concatenate([jnp.zeros((1, d), jnp.float32), rest])
    perm = jax.random.permutation(k_p, d)
    order = jnp.argsort(perm).astype(jnp.int32)
    return (x[:, perm], b0[perm][:, perm], m1[perm][:, perm], order)
