"""Mesh execution plan == local plan, bit for bit.

``fit_fn`` with a ``Partition`` compiles the full fit (ordering with
optional staged compaction, pruning, diagnostics) to one ``shard_map``
program; these tests pin its ``FitResult`` leaves to be *bit-identical*
to the local plan's across mesh shapes, compaction modes, backends, and
padding edge cases.

Multi-device cases run in a subprocess with XLA_FLAGS forcing 8 host
devices so the main test process keeps seeing exactly 1 device (per the
dry-run contract); the degenerate 1 x 1 mesh runs in-process.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest


def test_mesh_1x1_bit_identical_in_process():
    """The degenerate mesh plan (1 x 1) on the default device."""
    import jax.numpy as jnp

    from repro.core import api
    from repro.data.simulate import simulate_lingam

    gt = simulate_lingam(m=250, d=9, seed=0)
    x = jnp.asarray(gt.data)
    part = api.Partition(mesh=(("data", 1), ("model", 1)), chunk=64)
    for compaction in ("none", "staged"):
        cfg = api.FitConfig(compaction=compaction, min_stage=3)
        ref = api.fit_fn(x, cfg)
        got = api.fit_fn(
            x, api.FitConfig(compaction=compaction, min_stage=3,
                             partition=part)
        )
        assert np.array_equal(np.asarray(ref.order), np.asarray(got.order))
        assert np.array_equal(
            np.asarray(ref.adjacency), np.asarray(got.adjacency)
        )
        assert np.array_equal(
            np.asarray(ref.resid_var), np.asarray(got.resid_var)
        )


def test_batched_engine_rejects_partition():
    """vmap and mesh plans are orthogonal; nesting must fail loudly."""
    import jax.numpy as jnp

    from repro.core import api, batched

    part = api.Partition(mesh=(("data", 1), ("model", 1)))
    with pytest.raises(ValueError, match="mesh partition"):
        batched.fit_many(
            jnp.zeros((2, 64, 4)), api.FitConfig(partition=part)
        )


_PARITY_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax.numpy as jnp
    from repro.core import api
    from repro.data.simulate import simulate_lingam

    def leaves_equal(a, b):
        return (
            np.array_equal(np.asarray(a.order), np.asarray(b.order))
            and np.array_equal(
                np.asarray(a.adjacency), np.asarray(b.adjacency))
            and np.array_equal(
                np.asarray(a.resid_var), np.asarray(b.resid_var))
        )

    # Acceptance cell: (m=256, d=24), every mesh shape x compaction mode.
    gt = simulate_lingam(m=256, d=24, seed=0)
    x = jnp.asarray(gt.data)
    shapes = [
        ((("data", 1), ("model", 1))),
        ((("data", 2), ("model", 2))),
        ((("data", 4), ("model", 1))),
        ((("data", 8), ("model", 1))),
    ]
    for compaction in ("none", "staged"):
        ref = api.fit_fn(x, api.FitConfig(compaction=compaction))
        for shape in shapes:
            part = api.Partition(mesh=shape, chunk=64)
            got = api.fit_fn(
                x, api.FitConfig(compaction=compaction, partition=part))
            assert leaves_equal(ref, got), (shape, compaction)
            print("OK", dict(shape), compaction, flush=True)

    # Pallas row-tile kernel (interpret) under shard_map.
    for compaction in ("none", "staged"):
        ref = api.fit_fn(
            x, api.FitConfig(backend="pallas", compaction=compaction))
        got = api.fit_fn(x, api.FitConfig(
            backend="pallas", compaction=compaction,
            partition=api.Partition(mesh=(("data", 2), ("model", 2)),
                                    chunk=64),
        ))
        assert leaves_equal(ref, got), ("pallas", compaction)
        print("OK pallas", compaction, flush=True)

    # Non-divisible m/d: both axes need padding (d=23 over 2 pair
    # shards, m=250 over 2 x chunk=32 sample slots), OLS and lasso.
    gt = simulate_lingam(m=250, d=23, seed=2)
    x = jnp.asarray(gt.data)
    part = api.Partition(mesh=(("data", 2), ("model", 2)), chunk=32)
    for kw in (
        dict(),
        dict(prune_method="adaptive_lasso",
             prune_kwargs=dict(lam=0.05), prune_threshold=0.02),
    ):
        ref = api.fit_fn(
            x, api.FitConfig(compaction="staged", min_stage=4, **kw))
        got = api.fit_fn(x, api.FitConfig(
            compaction="staged", min_stage=4, partition=part, **kw))
        assert leaves_equal(ref, got), kw
        print("OK nondivisible", sorted(kw), flush=True)

    # Fully sharded finish (gather_finish=False): the dataset is never
    # reassembled, so the covariance reduction order differs — same
    # order, coefficients to fp32 reduction-order tolerance.
    scaled = api.Partition(
        mesh=(("data", 2), ("model", 2)), chunk=32, gather_finish=False)
    for kw in (
        dict(),
        dict(prune_method="adaptive_lasso",
             prune_kwargs=dict(lam=0.05), prune_threshold=0.02),
    ):
        ref = api.fit_fn(
            x, api.FitConfig(compaction="staged", min_stage=4, **kw))
        got = api.fit_fn(x, api.FitConfig(
            compaction="staged", min_stage=4, partition=scaled, **kw))
        assert np.array_equal(np.asarray(ref.order), np.asarray(got.order))
        # FISTA (400 iters) amplifies the psum reduction-order ulps, so
        # the lasso path needs a looser (still fp32-tight) tolerance.
        np.testing.assert_allclose(
            np.asarray(ref.adjacency), np.asarray(got.adjacency),
            atol=1e-4, rtol=1e-3)
        np.testing.assert_allclose(
            np.asarray(ref.resid_var), np.asarray(got.resid_var),
            atol=1e-4, rtol=1e-3)
        print("OK sharded-finish", sorted(kw), flush=True)
    print("MESH_FIT_OK")
    """
)


_ROUTING_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax.numpy as jnp
    from repro.core import api, VarLiNGAM
    from repro.serve.engine import CausalDiscoveryEngine, FitRequest
    from repro.data.simulate import simulate_lingam, simulate_var_stocks

    # Routing, not plan parity (the parity script pins bit-identity at
    # controlled cells): a partitioned config handed to a facade/engine
    # must produce exactly what the mesh plan produces directly —
    # fit_fn with the same config on the same data, bit for bit.
    part = api.Partition(mesh=(("data", 4), ("model", 2)), chunk=64)
    cfg = api.FitConfig(compaction="staged", partition=part)

    def assert_same_fit(result, data):
        direct = api.fit_fn(jnp.asarray(data, jnp.float32), cfg)
        assert np.array_equal(result.order, np.asarray(direct.order))
        assert np.array_equal(
            result.adjacency, np.asarray(direct.adjacency))
        assert np.array_equal(
            result.resid_var, np.asarray(direct.resid_var))

    # Engine: partitioned configs bypass the vmap micro-batcher and run
    # per-dataset through the mesh plan (shape-bucketed compile reuse).
    datasets = [simulate_lingam(m=256, d=12, seed=s).data for s in range(3)]
    mesh_eng = CausalDiscoveryEngine(cfg)
    for req in mesh_eng.run([FitRequest(data=d) for d in datasets]):
        assert_same_fit(req.result, req.data)
        assert sorted(req.result.order.tolist()) == list(range(12))
    print("OK engine", flush=True)

    # VarLiNGAM: the facade's residual ordering runs on the mesh; its
    # result_ must equal the mesh plan applied to its own VAR residuals,
    # and the recovered structure must match the local facade's quality.
    x, b0, m1 = simulate_var_stocks(m=2000, d=10, edge_prob=0.2, seed=0)
    v_mesh = VarLiNGAM(
        lags=1, prune_threshold=0.1, compaction="staged", partition=part
    ).fit(x)
    direct = api.fit_fn(
        jnp.asarray(v_mesh.residuals_, jnp.float32),
        v_mesh.to_config(),
    )
    assert np.array_equal(v_mesh.causal_order_, np.asarray(direct.order))
    assert np.array_equal(
        v_mesh.adjacency_matrices_[0], np.asarray(direct.adjacency))
    true_edges = b0 != 0
    est_edges = np.abs(v_mesh.adjacency_matrices_[0]) > 0.1
    tp = (true_edges & est_edges).sum()
    assert tp >= 0.6 * true_edges.sum(), (tp, true_edges.sum())
    print("OK varlingam", flush=True)
    print("MESH_ROUTING_OK")
    """
)


_PRUNE_SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import numpy as np
    import jax.numpy as jnp
    from repro.core import api
    from repro.data.simulate import simulate_lingam

    # Both plans run pruning.ols_from_cov on the same replicated
    # covariance (the lasso on top of it), so the leaves are bit-equal
    # for either pruner, padded pair axis included (d=23 over 4).
    kw = dict(prune_method=sys.argv[1])
    if kw["prune_method"] == "adaptive_lasso":
        kw.update(prune_kwargs=dict(lam=0.05), prune_threshold=0.02)
    x = jnp.asarray(simulate_lingam(m=300, d=23, seed=5).data)
    ref = api.fit_fn(x, api.FitConfig(compaction="staged", **kw))
    for shape in ((("data", 2), ("model", 2)), (("data", 1), ("model", 4))):
        got = api.fit_fn(x, api.FitConfig(
            compaction="staged", partition=api.Partition(mesh=shape, chunk=32),
            **kw))
        for leaf in ("order", "adjacency", "resid_var"):
            assert np.array_equal(np.asarray(getattr(ref, leaf)),
                                  np.asarray(getattr(got, leaf))), (shape, leaf)
    print("MESH_PRUNE_OK")
    """
)


def _run_subprocess(script, timeout=1200, args=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-c", script, *args],
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=timeout,
    )


@pytest.mark.slow
def test_mesh_fit_bit_identical_to_local():
    """Acceptance: mesh partition on 8 forced host devices returns
    bit-identical FitResult leaves to the local plan across mesh shapes,
    compaction modes, backends, and padding edges."""
    out = _run_subprocess(_PARITY_SCRIPT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_FIT_OK" in out.stdout


@pytest.mark.slow
def test_mesh_routing_engine_and_varlingam():
    """VarLiNGAM and CausalDiscoveryEngine route through the mesh plan."""
    out = _run_subprocess(_ROUTING_SCRIPT)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_ROUTING_OK" in out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("prune_method", ["ols", "adaptive_lasso"])
def test_mesh_prune_bit_identical(prune_method):
    """The mesh finish prunes with the local plan's code: bit-identical
    leaves for OLS and for the adaptive lasso."""
    out = _run_subprocess(_PRUNE_SCRIPT, args=(prune_method,))
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MESH_PRUNE_OK" in out.stdout
