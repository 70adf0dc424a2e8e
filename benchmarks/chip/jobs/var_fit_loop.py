"""Closed loop of one-shot VarLiNGAM fits: ``VarLiNGAM(lags, **fit).fit(X)``
on seeded VAR(1) panels (``datagen.var_panel``): the VAR least squares,
DirectLiNGAM on its residuals and the lag-matrix transform. Each fit's
order, B0 and VAR coefficients M1 are compared with the reference from the
same rows, and its lag matrix ``theta_1`` with ``(I - B0) M1`` formed from
the fit's own B0 and M1 in float64: the transform step on its own."""

from __future__ import annotations

import datagen
import fitloop
import refcheck


class Job(fitloop.FitLoop):
    def make(self, key):
        return datagen.var_panel(key, n_rows=self.m, d=self.d,
                                 **self.config["generator"])[0]

    def model(self):
        from repro.core import VarLiNGAM

        return VarLiNGAM(lags=int(self.config["lags"]), **self.config["fit"])

    def rows_fitted(self) -> int:
        return self.m - int(self.config["lags"])  # VAR residual rows

    def answer(self):
        return (self.facade.causal_order_, self.facade.adjacency_matrices_[0],
                self.facade.var_coefs_[0], self.facade.adjacency_matrices_[1])

    def compare(self, rows, answer, rng, control):
        order, b0, m1, theta1 = answer
        a, _, resid = refcheck.var1_ols(rows)
        gap, gap_mean = refcheck.order_gap_stats(
            resid, order, rng, int(self.traffic["check_random_steps"]),
            control)
        b0_ref = refcheck.adjacency_from_cov(
            refcheck.centered_cov(resid), order)
        if control:
            b0 = refcheck.adjacency_from_cov_ldl(
                refcheck.bf16_cov(resid), order)
            m1 = refcheck.control_var1_ols(rows)
            theta1 = refcheck.control_lag_transform(b0, m1)
        return {"order_gap": gap, "order_gap_mean": gap_mean,
                "b0_err": refcheck.rel_err(b0, b0_ref),
                "var_err": refcheck.rel_err(m1, a),
                "lag_err": refcheck.rel_err(
                    theta1, refcheck.lag_transform(b0, m1))}
