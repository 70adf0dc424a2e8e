#!/usr/bin/env python3
"""Record ``data/small_fit_scoped.xplane.pb``, the trace the per-stage
reduction (``xplane_scopes.py``) is tested on: one staged DirectLiNGAM fit
of a seeded 512 x 64 dataset and one staged VarLiNGAM fit of a seeded
513 x 48 panel, each a ``bench.graph`` of one ``bench.window``, as a
benchmark window is traced.

    python3 benchmarks/chip/tests/record_scoped_trace.py [OUT]   # on a TPU

``OUT`` defaults to ``data/small_fit_scoped.xplane.pb`` beside this file.
"""

import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
sys.path[:0] = [CHIP, os.path.join(os.path.dirname(os.path.dirname(CHIP)),
                                   "src")]

import jax  # noqa: E402

import datagen  # noqa: E402
import trace_reduce  # noqa: E402
from repro.core import DirectLiNGAM, VarLiNGAM  # noqa: E402


def main():
    if jax.devices()[0].platform != "tpu":
        print("record_scoped_trace: needs a TPU", file=sys.stderr)
        return 3
    x = datagen.gene_dataset(
        datagen.seed_key(1), m=512, d=64, edge_prob=0.1, weight=0.5,
        n_interventions=8, intervention_share=0.8, do_value=5.0)[0]
    panel = datagen.var_panel(
        datagen.seed_key(2), n_rows=513, d=48, edge_prob=0.05,
        b0_scale=0.5, ar_edge_prob=0.05, ar_scale=0.2)[0]
    fits = [(DirectLiNGAM(compaction="staged", prune_method="ols"), x),
            (VarLiNGAM(lags=1, compaction="staged", prune_method="ols"),
             panel)]
    for model, data in fits:
        model.fit(data)  # compile outside the trace
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(tmp, profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            for model, data in fits:
                with jax.profiler.TraceAnnotation("bench.graph"):
                    model.fit(data)
    out = (sys.argv[1] if len(sys.argv) > 1
           else os.path.join(HERE, "data", "small_fit_scoped.xplane.pb"))
    os.makedirs(os.path.dirname(out), exist_ok=True)
    shutil.copy(trace_reduce.find_xplane(tmp), out)
    shutil.rmtree(tmp, ignore_errors=True)
    print(out, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
