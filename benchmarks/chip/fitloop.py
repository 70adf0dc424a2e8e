"""The closed loop of one-shot fits that the fit jobs share.

Set-up makes ``datasets`` distinct seeded datasets of the configuration's
size on the device, plus one more for the warm-up fit. Each graph of the
window fits the next dataset (round robin) through a public facade, whose
own host reads end it. After the window a seeded sample of the fits is
compared with the plain reference.

A job drives its own window (:meth:`FitLoop.drive`) and reports its own
end-to-end values (:meth:`FitLoop.end_to_end`), so a job with another
loop, such as open-loop arrivals with a tail latency, comes as a module
of its own under ``jobs/`` and needs no change to ``run.py``.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

import datagen
import refcheck
import workcount


class FitLoop:
    """Subclasses say how a dataset is made (:meth:`make`), which facade
    fits it (:meth:`model`), what a fit answers (:meth:`answer`) and how
    an answer is compared (:meth:`compare`)."""

    def __init__(self, config, traffic, seed, limits):
        self.config = config
        self.traffic = traffic
        self.limits = limits
        self.seed = int(seed)
        self.m = int(config["m"])
        self.d = int(config["d"])
        self.results = []
        self.host_data = {}

    def make(self, key):
        raise NotImplementedError

    def model(self):
        raise NotImplementedError

    def answer(self):
        raise NotImplementedError

    def compare(self, x, answer, rng, control):
        raise NotImplementedError

    def rows_fitted(self) -> int:
        return self.m

    def setup(self, warm=True):
        import jax

        key = datagen.seed_key(self.seed)
        n = int(self.traffic["datasets"])
        self.data = [self.make(jax.random.fold_in(key, i))
                     for i in range(n + 1)]
        jax.block_until_ready(self.data)
        self.facade = self.model()
        if warm:
            self.facade.fit(self.data[n])  # warm-up graph, its own dataset

    def graph(self) -> bool:
        i = len(self.results) % (len(self.data) - 1)
        self.facade.fit(self.data[i])
        self.results.append((i, self.answer()))
        return True

    def drive(self, seconds, max_graphs=None, span=None):
        """The closed loop: graphs back to back until ``seconds`` have
        passed (or ``max_graphs`` are done). ``span(name)`` marks the
        window and each graph in a trace. Returns (graphs, failed,
        elapsed seconds)."""
        span = span or (lambda name: contextlib.nullcontext())
        graphs = failed = 0
        with span("bench.window"):
            t0 = time.perf_counter()
            while True:
                with span("bench.graph"):
                    ok = self.graph()
                graphs += 1
                failed += 0 if ok else 1
                elapsed = time.perf_counter() - t0
                if elapsed >= seconds or (max_graphs and graphs >= max_graphs):
                    break
        return graphs, failed, elapsed

    def end_to_end(self, graphs, elapsed):
        """The window's end-to-end values: seconds per graph, the whole
        window over the whole graphs completed in it."""
        return {self.traffic["graph_metric"]: elapsed / graphs}

    def work(self):
        m = self.rows_fitted()
        staged = self.config["fit"].get("compaction") == "staged"
        return {
            "m": m,
            "d": self.d,
            "pair_samples": workcount.pair_samples(m, self.d),
            "padded_pair_samples": (
                workcount.staged_padded_pair_samples(m, self.d)
                if staged else None),
        }

    def release(self):
        """Keep on the host what the check needs; free the program."""
        rng = np.random.default_rng([self.seed, 1])
        k = min(int(self.traffic["check_graphs"]), len(self.results))
        self.sample = sorted(rng.choice(len(self.results), k, replace=False))
        for g in self.sample:
            i = self.results[g][0]
            if i not in self.host_data:
                self.host_data[i] = np.asarray(self.data[i])
        del self.facade, self.data

    def check(self, control=False):
        """The worst of each compared number over the sampled fits; with
        ``control``, those of the bfloat16 reference in the program's
        place (its picks at the program's steps, its matrices)."""
        rng = np.random.default_rng([self.seed, 2])
        worst = {name: 0.0 for name in self.limits}
        for g in self.sample:
            i, answer = self.results[g]
            if not refcheck.is_permutation(answer[0], self.d):
                got = {name: float("inf") for name in worst}
            else:
                got = self.compare(self.host_data[i], answer, rng, control)
            for name, v in got.items():
                v = float("inf") if v != v else v  # NaN reads as failed
                worst[name] = max(worst.get(name, 0.0), v)
        self.readings = worst  # every number compared, limited or not
        return refcheck.combine(worst, self.limits)
