"""Seeded knockout screens on the device: :func:`datagen.gene_dataset`'s
rows, with the knockout target of each row as a mask.

``gene_dataset`` draws, for the first ``intervention_share`` of the rows,
one target gene out of ``n_interventions`` and sets it to ``do_value``
(``do(x_g = do_value)``), then permutes the gene columns. Its data are
used unchanged: :func:`gene_dataset_int` calls it, then draws the same
targets again from the same key split (``jax.random.split(key, 4)[2]``)
and maps them through the same permutation (``[3]``), so the rows are
bit-identical to the pooled cell's for the same key and ``intervened[s, c]``
is True exactly where row ``s``'s knockout set column ``c``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import datagen


@functools.partial(
    jax.jit, static_argnames=("m", "d", "n_interventions",
                              "intervention_share"))
def knockout_mask(key, *, m, d, n_interventions, intervention_share):
    """(m, d) bool: True where the row's knockout set the column."""
    _, _, k_t, k_p = jax.random.split(key, 4)
    n_int_rows = int(intervention_share * m)
    target = jnp.where(
        jnp.arange(m) < n_int_rows,
        jax.random.randint(k_t, (m,), 0, n_interventions),
        -1,
    )
    perm = jax.random.permutation(k_p, d)   # column c holds gene perm[c]
    return target[:, None] == perm[None, :]


def gene_dataset_int(key, *, m, d, edge_prob, weight, n_interventions,
                     intervention_share, do_value):
    """``(x, intervened)``: ``gene_dataset``'s (m, d) rows and their
    knockout mask."""
    x = datagen.gene_dataset(
        key, m=m, d=d, edge_prob=edge_prob, weight=weight,
        n_interventions=n_interventions,
        intervention_share=intervention_share, do_value=do_value)[0]
    return x, knockout_mask(key, m=m, d=d, n_interventions=n_interventions,
                            intervention_share=intervention_share)
