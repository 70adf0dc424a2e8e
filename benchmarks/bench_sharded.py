"""Mesh-plan sweep: ordering/fit time per mesh shape vs the 1-device oracle.

Sweeps the mesh shapes 1x1, 2x2, 4x1, 8x1 over 8 forced host CPU
devices (a ``JAX_PLATFORMS=cpu`` subprocess, so the parent keeps its
default device and any accelerator to itself) and times, per shape:

  * the sharded ordering (``make_sharded_causal_order`` — the 96% hot
    path) and its per-step cost,
  * the full sharded fit through ``fit_fn`` with a ``Partition``
    (ordering with staged compaction + row-sharded pruning),

against the single-device ``causal_order`` oracle, reporting order
agreement. (Exact agreement is pinned by tests at controlled cells; at
arbitrary sizes a genuinely near-tied argmax step may resolve
differently between the local blocked kernel and the chunked row-tile
kernel — ``order_n_disagree`` makes that visible rather than failing.)
On forced host devices the collectives are memcpys, so this measures
plan overhead, not speedup — the point is the machine-readable perf
trajectory (``benchmarks.run`` mirrors these rows into
``BENCH_sharded.json`` at the repo root) that a real multi-chip run
slots into.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

_SCRIPT = textwrap.dedent(
    """
    import json, sys, time
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax.numpy as jnp
    from repro.core import api
    from repro.core.ordering import causal_order
    from repro.core.sharded import make_sharded_causal_order
    from repro.data.simulate import simulate_lingam
    from repro.launch.mesh import mesh_from_spec

    m, d, chunk = (int(a) for a in sys.argv[1:4])
    gt = simulate_lingam(m=m, d=d, seed=0)
    x = jnp.asarray(gt.data)

    causal_order(x).block_until_ready()  # compile
    t0 = time.perf_counter()
    ref = causal_order(x)
    ref.block_until_ready()
    t_oracle = time.perf_counter() - t0
    ref = np.asarray(ref)

    rows = []
    for shape in (
        (("data", 1), ("model", 1)),
        (("data", 2), ("model", 2)),
        (("data", 4), ("model", 1)),
        (("data", 8), ("model", 1)),
    ):
        sizes = dict(shape)
        label = f"{sizes['data']}x{sizes['model']}"
        mesh = mesh_from_spec(shape)
        fn, m_pad, d_pad = make_sharded_causal_order(mesh, m, d, chunk=chunk)
        x_pad = jnp.pad(x, ((0, m_pad - m), (0, d_pad - d)))
        fn(x_pad).block_until_ready()  # compile
        t0 = time.perf_counter()
        order = fn(x_pad)
        order.block_until_ready()
        t_order = time.perf_counter() - t0

        part = api.Partition(mesh=shape, chunk=chunk)
        cfg = api.FitConfig(compaction="staged", partition=part)
        api.fit_fn(x, cfg).adjacency.block_until_ready()  # compile
        t0 = time.perf_counter()
        res = api.fit_fn(x, cfg)
        res.adjacency.block_until_ready()
        t_fit = time.perf_counter() - t0

        got = np.asarray(order)[:d]
        rows.append({
            "mesh": label, "m": m, "d": d,
            "order_s": t_order, "order_step_ms": 1e3 * t_order / d,
            "fit_s": t_fit, "oracle_order_s": t_oracle,
            "order_matches_oracle": bool(np.array_equal(got, ref)),
            "order_n_disagree": int((got != ref).sum()),
        })
    print("BENCH_JSON:" + json.dumps(rows), flush=True)
    """
)


def run(quick: bool = True):
    m, d, chunk = (2048, 32, 256) if quick else (16384, 96, 512)
    env = dict(os.environ)
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.pop("XLA_FLAGS", None)
    # The child's virtual devices are CPU devices: it must never contend
    # with the parent for an accelerator, which one process holds.
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _SCRIPT, str(m), str(d), str(chunk)],
        capture_output=True, text=True, env=env, cwd=root, timeout=3600,
    )
    if out.returncode != 0:
        raise RuntimeError(f"bench_sharded subprocess failed:\n"
                           f"{out.stderr[-3000:]}")
    payload = next(
        line for line in out.stdout.splitlines()
        if line.startswith("BENCH_JSON:")
    )
    rows = json.loads(payload[len("BENCH_JSON:"):])
    for r in rows:
        print(
            f"bench_sharded,mesh={r['mesh']},m={r['m']},d={r['d']},"
            f"order={r['order_s']:.3f}s,step={r['order_step_ms']:.1f}ms,"
            f"fit={r['fit_s']:.3f}s,oracle={r['oracle_order_s']:.3f}s,"
            f"match={r['order_matches_oracle']},"
            f"n_disagree={r['order_n_disagree']}"
        )
    return rows
