"""Closed loop of one-shot DirectLiNGAM fits: ``DirectLiNGAM(**fit).fit(X)``
on seeded LiNGAM datasets (``datagen.gene_dataset``); each fit's order and
adjacency are compared with the reference."""

from __future__ import annotations

import datagen
import fitloop
import refcheck


class Job(fitloop.FitLoop):
    def make(self, key):
        return datagen.gene_dataset(key, m=self.m, d=self.d,
                                    **self.config["generator"])[0]

    def model(self):
        from repro.core import DirectLiNGAM

        return DirectLiNGAM(**self.config["fit"])

    def answer(self):
        return self.facade.causal_order_, self.facade.adjacency_

    def compare(self, x, answer, rng, control):
        order, b = answer
        gap, gap_mean = refcheck.order_gap_stats(
            x, order, rng, int(self.traffic["check_random_steps"]), control)
        b_ref = refcheck.adjacency_from_cov(refcheck.centered_cov(x), order)
        if control:
            b = refcheck.adjacency_from_cov_ldl(refcheck.bf16_cov(x), order)
        return {"order_gap": gap, "order_gap_mean": gap_mean,
                "adjacency_err": refcheck.rel_err(b, b_ref)}
