"""Closed loop of interventional DirectLiNGAM fits:
``DirectLiNGAM(**fit).fit(X, intervened=M)`` on seeded knockout screens
(``datagen_int.gene_dataset_int``: ``datagen.gene_dataset``'s rows and the
mask of the gene each row's knockout set). Each fit's order and adjacency
are compared with the masked reference (``refcheck_int``).

Besides the compared numbers, each check reads (not compared)
``misoriented_edge_share``: the share of the generator's true edges that
the fit's order puts the wrong way round. With ``control`` the check also
reads the pooled program's fit of the same rows, the mask ignored, under
``pooled.<number>``.
"""

from __future__ import annotations

import numpy as np

import datagen
import datagen_int
import fitloop
import refcheck
import refcheck_int
import workcount_int


class _FitWithMask:
    """The facade as the loop calls it: ``fit(data)``, ``data`` the pair
    ``(x, intervened)``."""

    def __init__(self, model):
        self.model = model

    def fit(self, data):
        x, intervened = data
        return self.model.fit(x, intervened=intervened)


class Job(fitloop.FitLoop):
    def make(self, key):
        return datagen_int.gene_dataset_int(key, m=self.m, d=self.d,
                                            **self.config["generator"])

    def model(self):
        from repro.core import DirectLiNGAM

        return _FitWithMask(DirectLiNGAM(**self.config["fit"]))

    def answer(self):
        fit = self.facade.model
        return fit.causal_order_, fit.adjacency_

    def work(self):
        import jax

        out = super().work()
        out["device_kind"] = jax.devices()[0].device_kind
        out["masked_moment_bytes"] = workcount_int.masked_kernel_bytes(
            self.m, self.d)
        return out

    def release(self):
        """As the loop's, keeping with each sampled dataset its mask and
        its true adjacency (made again from its key)."""
        data = self.data
        self.data = [x for x, _ in data]
        super().release()
        key = datagen.seed_key(self.seed)
        import jax

        for i, x in self.host_data.items():
            b_true = datagen.gene_dataset(
                jax.random.fold_in(key, i), m=self.m, d=self.d,
                **self.config["generator"])[1]
            self.host_data[i] = (x, np.asarray(data[i][1]),
                                 np.asarray(b_true))

    def compare(self, data, answer, rng, control):
        x, intervened, b_true = data
        valid = ~intervened
        order, b = answer
        steps = int(self.traffic["check_random_steps"])
        gap, gap_mean = refcheck_int.order_gap_stats(
            x, valid, order, rng, steps, control)
        b_ref = refcheck_int.masked_ols(x, valid, order)
        if control:
            b = refcheck_int.control_masked_ols(x, valid, order)
        out = self._numbers(gap, gap_mean, b, b_ref, intervened)
        out["misoriented_edge_share"] = refcheck_int.misoriented_share(
            order, b_true)
        if control:
            out.update(self._pooled(x, valid, b_true, rng, steps))
        return out

    @staticmethod
    def _numbers(gap, gap_mean, b, b_ref, intervened):
        return {"order_gap": gap, "order_gap_mean": gap_mean,
                "adjacency_err": refcheck.rel_err(b, b_ref),
                "intervened_adj_err": refcheck_int.rows_err(
                    b, b_ref, np.nonzero(intervened.any(axis=0))[0])}

    def _pooled(self, x, valid, b_true, rng, steps):
        """The mask ignored: the pooled program's fit of the same rows,
        judged by the masked reference."""
        from repro.core import DirectLiNGAM

        fit = DirectLiNGAM(**self.config["fit"]).fit(x)
        order = fit.causal_order_
        gap, gap_mean = refcheck_int.order_gap_stats(
            x, valid, order, rng, steps)
        b_ref = refcheck_int.masked_ols(x, valid, order)
        out = self._numbers(gap, gap_mean, fit.adjacency_, b_ref, ~valid)
        out["misoriented_edge_share"] = refcheck_int.misoriented_share(
            order, b_true)
        return {"pooled." + k: v for k, v in out.items()}
