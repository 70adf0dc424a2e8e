"""The main path's Pallas kernels and the local fit compile for a TPU v5e.

Compiled for one chip of a described ``v5e:2x2`` topology with
``interpret=False``: nothing runs, but the TPU compiler refuses what
interpret mode accepts — blocks off the (8, 128) tiling, kernels that
overflow VMEM, programs that do not fit the chip's memory. The topology
is described inside a fixture (the TPU library may be loaded by one
process at a time), and the tests skip only there.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import api
from repro.kernels import ops
from repro.kernels.tune import registry

HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


_PALLAS = dict(backend="pallas", interpret=False)

# (case, kernel call, argument shapes): the widths the main path runs.
# Each compiles with the dispatcher's heuristic plan; a plan whose working
# set overflows the chip's scoped VMEM fails here (RESOURCE_EXHAUSTED).
_KERNELS = {
    # gene-964: d_pad 1024, m_pad 65,536, blocks (8, 128, 2048).
    "pair_gene964": (
        lambda x, c: ops.pairwise_moments(x, c, **_PALLAS),
        [(65_164, 964), (964, 964)],
    ),
    # The benchmark's cells: gene-964 at 4,096 rows (blocks fill the
    # samples exactly) and stocks-487 (3,999 rows, a masked last chunk).
    "pair_4096x964": (
        lambda x, c: ops.pairwise_moments(x, c, **_PALLAS),
        [(4096, 964), (964, 964)],
    ),
    "pair_3999x487": (
        lambda x, c: ops.pairwise_moments(x, c, **_PALLAS),
        [(3999, 487), (487, 487)],
    ),
    # 1m-100 width, one 2,048-sample block: one 104-column block.
    "pair_d100": (
        lambda x, c: ops.pairwise_moments(x, c, **_PALLAS),
        [(2048, 100), (100, 100)],
    ),
    # One pair-axis row tile of the 2x2 mesh plan at gene-964.
    "rows_d964_tile482": (
        lambda x, c: ops.pairwise_moment_sums_rows(
            x, c, 482, 482, chunk=512, **_PALLAS
        ),
        [(32_768, 964), (964, 964)],
    ),
    # The masked pair tile of the interventional fit at gene-964's
    # benchmark width: data, validity, the pair maps and counts.
    "masked_4096x964": (
        lambda z, v, c, a, g, n: ops.pairwise_moments_masked(
            z, v, c, a, g, n, **_PALLAS
        ),
        [(4096, 964), (4096, 964)] + [(964, 964)] * 4,
    ),
    # Fused standardize + moments (Partition(fused_standardize=True)).
    "fused_d964": (
        lambda x, mu, rstd, c: ops.fused_moment_rows(
            x, mu, rstd, c, 0, 8, interpret=False
        ),
        [(4096, 964), (964,), (964,), (964, 964)],
    ),
}


@pytest.mark.parametrize("case", sorted(_KERNELS))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = _KERNELS[case]
    args = [_sds(one_chip, *s) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_staged_local_fit_compiles_for_v5e(one_chip):
    """The serving default (staged compaction, whose stage widths fall
    below 128) at stocks-487, as one program that fits the chip."""
    cfg = api.FitConfig(compaction="staged", **_PALLAS)
    compiled = api._fit_local.lower(_sds(one_chip, 4000, 487), cfg).compile()
    assert compiled.as_text().count("tpu_custom_call") > 1
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


def test_fit_kernel_keeps_its_name_under_the_moments_scope(one_chip):
    """In the compiled fit, the pair-tile kernel's custom call has its
    fixed name and carries the ``lingam.moments`` scope in its op_name:
    what a device trace reads per op."""
    cfg = api.FitConfig(compaction="staged", **_PALLAS)
    text = api._fit_local.lower(_sds(one_chip, 512, 64), cfg).compile(
    ).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert line.lstrip().startswith("%pairwise_moments_pallas"), line
        (op_name,) = re.findall(r'op_name="([^"]*)"', line)
        assert "/lingam.moments/" in op_name, op_name


def test_masked_kernel_plan_counts_its_mask_blocks_within_vmem():
    """The masked kernel's heuristic blocks at 4,096 x 964, with its two
    validity blocks and two more per-pair blocks counted, fit the VMEM
    budget that the compile above holds them to."""
    plan = registry.dispatch_heuristic(
        "pairwise_moments_masked", (4096, 964), backend="pallas")
    masked = registry.vmem_bytes(plan.bi, plan.bj, plan.bm, masked=True)
    assert registry.vmem_bytes(plan.bi, plan.bj, plan.bm) < masked
    assert masked <= registry._VMEM_BUDGET


def test_masked_staged_fit_compiles_for_v5e(one_chip):
    """The interventional fit of the benchmark's cell (4,096 x 964,
    staged, one masked kernel a stage) as one program that fits the chip:
    the masked kernel under ``lingam.moments``, its pair statistics under
    ``lingam.mask_stats``."""
    cfg = api.FitConfig(compaction="staged", **_PALLAS)
    mask = jax.ShapeDtypeStruct((4096, 964), jnp.bool_, sharding=one_chip)
    compiled = api._fit_local.lower(
        _sds(one_chip, 4096, 964), cfg, mask).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) > 1
    for line in calls:
        assert line.lstrip().startswith("%pairwise_moments_masked"), line
        (op_name,) = re.findall(r'op_name="([^"]*)"', line)
        assert "/lingam.moments/" in op_name, op_name
    assert "/lingam.mask_stats/" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
