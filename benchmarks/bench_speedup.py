"""Paper Fig. 2 analogue: runtime scaling of the causal-ordering
sub-procedure, sequential (numpy pair loop) vs parallel (vectorized jnp /
Pallas, interpreted off-chip: ``pallas_interpret`` says which), over a
(samples x dims) grid; plus the fraction of total DirectLiNGAM runtime
spent in ordering.

On this CPU container the "parallel" rows measure the vectorized
single-core implementations (the TPU speed-up story is the §Roofline
analysis); the *speed-up column still shows the algorithmic win* of batched
vectorization over the pair loop — the same effect the paper's GPU kernel
exploits (32x on an RTX 6000 Ada).
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.baselines import sequential_lingam as seq
from repro.core.ordering import causal_order
from repro.data.simulate import simulate_lingam
from repro.kernels.tune.registry import resolve_interpret


def _time(fn, *args, reps=1):
    fn(*args)  # warmup / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    if hasattr(out, "block_until_ready"):
        out.block_until_ready()
    return (time.perf_counter() - t0) / reps


def run(quick: bool = True):
    grid = (
        [(1_000, 8), (1_000, 16), (5_000, 16), (5_000, 32)]
        if quick
        else [(10_000, 8), (10_000, 16), (10_000, 32), (50_000, 32),
              (10_000, 64), (100_000, 16)]
    )
    rows = []
    for m, d in grid:
        gt = simulate_lingam(m=m, d=d, seed=0)
        x = gt.data

        t_seq = _time(lambda: seq.causal_order_sequential(x))
        t_par = _time(
            lambda: causal_order(jax.numpy.asarray(x), backend="blocked")
        )
        t_pal = _time(
            lambda: causal_order(jax.numpy.asarray(x), backend="pallas")
        )
        # ordering fraction of the full sequential fit (paper: 96%)
        t0 = time.perf_counter()
        order = seq.causal_order_sequential(x)
        t_ord = time.perf_counter() - t0
        t0 = time.perf_counter()
        seq.ols_adjacency_sequential(x, order)
        t_reg = time.perf_counter() - t0
        frac = t_ord / (t_ord + t_reg)

        rows.append({
            "m": m, "d": d,
            "sequential_s": t_seq,
            "parallel_blocked_s": t_par,
            "parallel_pallas_s": t_pal,
            "pallas_interpret": resolve_interpret(None),
            "speedup_blocked": t_seq / t_par,
            "ordering_fraction": frac,
        })
        print(
            f"bench_speedup,m={m},d={d},seq={t_seq:.3f}s,"
            f"par={t_par:.3f}s,speedup={t_seq/t_par:.1f}x,"
            f"ordering_frac={frac:.3f}"
        )
    return rows
