"""Pallas pairwise-stats kernel vs the pure-jnp oracle: shape/dtype sweep."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.pairwise_stats import (
    pairwise_moments_masked_pallas,
    pairwise_moments_pallas,
)

RNG = np.random.default_rng(42)


def _make(m, d, dtype=np.float32, dist="laplace"):
    if dist == "laplace":
        x = RNG.laplace(size=(m, d))
    elif dist == "tiny":
        # Ten samples a column at full scale, in pairs of opposite sign so
        # that the mean stays near 0: the rest standardize below 1e-3, the
        # pairs' correlations are near 0, and so is most |u|.
        x = RNG.laplace(size=(m, d)) * 1e-4
        rows = np.argsort(RNG.uniform(size=(m, d)), axis=0)[:10]
        a = RNG.laplace(size=(5, d))
        x[rows, np.arange(d)] = np.concatenate([a, -a])
    elif dist == "heavy":
        # One outlier a column standardizes to about sqrt(m) (> 20 at
        # m = 500): log cosh at |u| > 20, where exp(-2|u|) underflows.
        x = RNG.laplace(size=(m, d))
        x[RNG.integers(m, size=d), np.arange(d)] = 300.0
    else:
        x = RNG.uniform(size=(m, d))
    x = x.astype(dtype)
    xs = ops.standardize(jnp.asarray(x, dtype=jnp.float32))
    c = ops.correlation(xs)
    return xs, c


def _offdiag_close(a, b, d, atol):
    mask = 1.0 - jnp.eye(d)
    np.testing.assert_allclose(
        np.asarray(a * mask), np.asarray(b * mask), atol=atol, rtol=0
    )


@pytest.mark.parametrize(
    "m,d",
    [(64, 4), (100, 5), (257, 10), (511, 16), (1000, 33), (2048, 64), (4096, 130)],
)
def test_pallas_matches_oracle_shapes(m, d):
    xs, c = _make(m, d)
    m1r, m2r = ref.pairwise_moments_ref(xs, c)
    m1p, m2p = ops.pairwise_moments(xs, c, backend="pallas", interpret=True)
    _offdiag_close(m1r, m1p, d, atol=2e-6)
    _offdiag_close(m2r, m2p, d, atol=2e-6)


@pytest.mark.parametrize("m,d", [(300, 7), (1024, 24)])
def test_blocked_matches_oracle(m, d):
    xs, c = _make(m, d)
    m1r, m2r = ref.pairwise_moments_ref(xs, c)
    m1b, m2b = ops.pairwise_moments(xs, c, backend="blocked")
    _offdiag_close(m1r, m1b, d, atol=2e-6)
    _offdiag_close(m2r, m2b, d, atol=2e-6)


@pytest.mark.parametrize("bi,bj,bm", [(8, 8, 256), (8, 128, 512), (16, 16, 256)])
def test_pallas_block_shape_sweep(bi, bj, bm):
    m, d = 777, 40
    xs, c = _make(m, d)
    m1r, m2r = ref.pairwise_moments_ref(xs, c)
    d_pad = ((d + max(bi, bj) - 1) // max(bi, bj)) * max(bi, bj)
    m_pad = ((m + bm - 1) // bm) * bm
    xt = jnp.pad(xs.T, ((0, d_pad - d), (0, m_pad - m)))
    cp = jnp.pad(c, ((0, d_pad - d), (0, d_pad - d)))
    m1p, m2p = pairwise_moments_pallas(
        xt, cp, m_total=m, d_total=d, bi=bi, bj=bj, bm=bm, interpret=True
    )
    _offdiag_close(m1r, m1p[:d, :d], d, atol=2e-6)
    _offdiag_close(m2r, m2p[:d, :d], d, atol=2e-6)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("dist", ["laplace", "uniform", "tiny", "heavy"])
def test_pallas_dtype_dist_sweep(dtype, dist):
    m, d = 500, 12
    xs, c = _make(m, d, dtype=dtype, dist=dist)
    if dist == "tiny":
        assert np.median(np.abs(xs)) < 1e-3
    if dist == "heavy":
        assert np.min(np.max(np.abs(xs), axis=0)) > 20.0
    m1r, m2r = ref.pairwise_moments_ref(xs, c)
    m1p, m2p = ops.pairwise_moments(xs, c, backend="pallas", interpret=True)
    _offdiag_close(m1r, m1p, d, atol=2e-6)
    _offdiag_close(m2r, m2p, d, atol=2e-6)


def test_bf16_input_upcast():
    m, d = 512, 16
    x = RNG.laplace(size=(m, d)).astype(np.float32)
    xs32 = ops.standardize(jnp.asarray(x))
    c32 = ops.correlation(xs32)
    xs16 = xs32.astype(jnp.bfloat16)
    m1r, _ = ref.pairwise_moments_ref(xs32, c32)
    m1p, _ = ops.pairwise_moments(
        xs16.astype(jnp.float32), c32, backend="pallas", interpret=True
    )
    # bf16 data has ~3 decimal digits; moments agree loosely.
    _offdiag_close(m1r, m1p, d, atol=1e-2)


# The shared moment sums against the oracle, through both kernels that
# run them. (m, d, bi, bm): samples that fill the sample blocks exactly
# (no mask is emitted), a ragged last block after three full ones, one
# block of 256 that holds fewer than a chunk of samples (a chunk with
# none: its log 2 count clamps at 0) or a chunk and a part, and row
# blocks of 8, 32 and 128 one variable past a lane tile (the pair-tile
# grid stops at the last row block that holds a variable).
_MOMENT_CASES = {
    "filled": (512, 20, 8, 256),
    "ragged": (3 * 256 + 37, 20, 8, 256),
    "ragged_m64": (64, 20, 8, 256),
    "ragged_m200": (200, 20, 8, 256),
    "bi8_d129": (300, 129, 8, 128),
    "bi32_d129": (300, 129, 32, 128),
    "bi128_d129": (300, 129, 128, 128),
}


def _kernel_primitives(fn, *args):
    """Count of each primitive anywhere in fn's one ``pallas_call`` body."""
    import collections

    import jax

    def kernels(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["jaxpr"]
            for sub in _subjaxprs(eqn.params):
                yield from kernels(sub)

    def count(jaxpr, counts):
        for eqn in jaxpr.eqns:
            counts[eqn.primitive.name] += 1
            for sub in _subjaxprs(eqn.params):
                count(sub, counts)
        return counts

    (body,) = kernels(jax.make_jaxpr(fn)(*args).jaxpr)
    return count(body, collections.Counter())


@pytest.mark.parametrize("kernel", ["pair", "rows"])
@pytest.mark.parametrize("case", sorted(_MOMENT_CASES))
def test_moment_kernels_match_oracle(kernel, case):
    m, d, bi, bm = _MOMENT_CASES[case]
    xs, c = _make(m, d)
    m1r, m2r = ref.pairwise_moments_ref(xs, c)
    bj = 128 if d > 128 else d + (-d) % 8
    if kernel == "pair":
        def fn(x, c):
            return ops.pairwise_moments(
                x, c, backend="pallas", interpret=True, blocks=(bi, bj, bm))

        m1, m2 = fn(xs, c)
        _offdiag_close(m1r, m1, d, atol=2e-6)
        _offdiag_close(m2r, m2, d, atol=2e-6)
    else:
        start, tile = 1, d - 1  # a tile that straddles the row blocks

        def fn(x, c):
            return ops.pairwise_moment_sums_rows(
                x, c, start, tile, backend="pallas", interpret=True,
                blocks=(bi, bj, bm))

        s1, s2 = fn(xs, c)
        assert s1.shape == (tile, d)
        mask = 1.0 - np.eye(d)[start:]
        for got, want in ((s1, m1r), (s2, m2r)):
            np.testing.assert_allclose(
                np.asarray(got) * mask, np.asarray(want)[start:] * m * mask,
                atol=2e-6 * m, rtol=0,
            )
    # a mask (select) only where the last sample block is ragged
    masks = _kernel_primitives(fn, xs, c)["select_n"]
    assert (masks > 0) == (m % bm != 0)


@pytest.mark.parametrize("kernel", ["pair", "masked"])
def test_kernel_body_takes_log_not_log1p(kernel):
    """The kernel bodies take log cosh's log on the EUP (``log``), not the
    VALU-expanded ``log1p`` (see ``pairwise_stats``'s module docstring)."""
    import functools

    d, m = 16, 256
    x_t = jnp.zeros((d, m), jnp.float32)
    pairs = jnp.zeros((d, d), jnp.float32)
    blocks = dict(m_total=200, d_total=d, bi=8, bj=d, bm=m)
    if kernel == "pair":
        fn, args = pairwise_moments_pallas, (x_t, pairs)
    else:
        fn, args = pairwise_moments_masked_pallas, (x_t, x_t) + (pairs,) * 3
    prims = _kernel_primitives(functools.partial(fn, **blocks), *args)
    assert prims["log"] > 0
    assert prims["log1p"] == 0


# Padding edges: tile / d / m just above and below the block multiples
# (bi=8, bj=8, bm=128/256), pinned against the blocked-oracle sums. The
# ops wrappers pad to the blocks and mask/slice the excess; these
# cells would silently corrupt the edge rows/columns if the padding or
# the m_total mask were off by one.
_EDGE_CELLS = [
    # (tile, d, m): d and m straddle block multiples; tile straddles bi.
    (7, 9, 127),    # all just below/above the 8/128 quanta
    (8, 16, 129),   # m one past a bm sub-chunk
    (9, 15, 255),   # tile just above bi, m just below 2*128
    (8, 17, 257),   # d one past 2*8, m one past 2*128
    (16, 16, 128),  # exact multiples (no-padding control cell)
    (8, 16, 64),    # m under one chunk (fused: a 256 block of 64)
    (9, 17, 200),   # m a chunk and a part (fused: a 256 block of 200)
]


def _rows_oracle_sums(xs, c, tile):
    """Blocked-oracle row sums: means * m, first `tile` rows."""
    m = xs.shape[0]
    m1r, m2r = ref.pairwise_moments_ref(xs, c)
    return np.asarray(m1r)[:tile] * m, np.asarray(m2r)[:tile] * m


@pytest.mark.parametrize("tile,d,m", _EDGE_CELLS)
def test_rows_padding_edges_vs_blocked_oracle(tile, d, m):
    xs, c = _make(m, d)
    s1r, s2r = _rows_oracle_sums(xs, c, tile)
    # force blocks that do NOT divide the shape, so the wrapper must pad
    # every axis and mask the sample tail
    s1, s2 = ops.pairwise_moment_sums_rows(
        xs, c, 0, tile, backend="pallas", interpret=True, blocks=(8, 8, 128)
    )
    assert s1.shape == (tile, d)
    mask = 1.0 - np.eye(tile, d)
    np.testing.assert_allclose(
        np.asarray(s1) * mask, s1r * mask, atol=2e-6 * m, rtol=0
    )
    np.testing.assert_allclose(
        np.asarray(s2) * mask, s2r * mask, atol=2e-6 * m, rtol=0
    )
    # the blocked backend is exact at the same cells (chunk > m forces
    # a single padded slab)
    b1, b2 = ops.pairwise_moment_sums_rows(
        xs, c, 0, tile, chunk=64, backend="blocked"
    )
    np.testing.assert_allclose(
        np.asarray(b1) * mask, s1r * mask, atol=2e-6 * m, rtol=0
    )


@pytest.mark.parametrize("tile,d,m", _EDGE_CELLS)
def test_fused_padding_edges_vs_blocked_oracle(tile, d, m):
    x = RNG.laplace(size=(m, d)).astype(np.float32)
    xj = jnp.asarray(x)
    xs = ops.standardize(xj)
    c = ops.correlation(xs)
    s1r, s2r = _rows_oracle_sums(xs, c, tile)
    mu = jnp.mean(xj, axis=0)
    rstd = 1.0 / jnp.maximum(jnp.std(xj, axis=0), 1e-12)
    s1, s2 = ops.fused_moment_rows(
        xj, mu, rstd, c, 0, tile, interpret=True, blocks=(8, 8, 256)
    )
    assert s1.shape == (tile, d)
    mask = 1.0 - np.eye(tile, d)
    np.testing.assert_allclose(
        np.asarray(s1) * mask, s1r * mask, atol=4e-6 * m, rtol=0
    )
    np.testing.assert_allclose(
        np.asarray(s2) * mask, s2r * mask, atol=4e-6 * m, rtol=0
    )


def test_chunked_padding_edge_vs_oracle():
    """Chunk-accumulated sums at a non-divisible window length."""
    m, d = 333, 10
    xs, c = _make(m, d)
    m1r, m2r = ref.pairwise_moments_ref(xs, c)
    for backend in ("blocked", "pallas"):
        m1, m2 = ops.pairwise_moments_chunked(
            xs, c, chunk=128, backend=backend, interpret=True
        )
        _offdiag_close(m1r, m1, d, atol=2e-6)
        _offdiag_close(m2r, m2, d, atol=2e-6)


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_fused_kernel_matches_oracle(dtype):
    """Fused standardize+moments kernel (raw X in, optional bf16 streaming)
    vs the standardize-then-oracle pipeline (§Perf C2+C3)."""
    from repro.kernels.fused_stats import fused_moment_sums

    m, d, tile = 512, 16, 8
    x = RNG.laplace(size=(m, d)).astype(np.float32)
    xs = ops.standardize(jnp.asarray(x))
    c = ops.correlation(xs)
    m1r, m2r = ref.pairwise_moments_ref(xs, c)

    mu = jnp.mean(jnp.asarray(x), axis=0)
    sd = jnp.maximum(jnp.std(jnp.asarray(x), axis=0), 1e-12)
    rstd = 1.0 / sd
    xr = jnp.asarray(x).T  # (d, m) raw
    if dtype == "bfloat16":
        xr = xr.astype(jnp.bfloat16)
    mu, rstd = mu[:, None], rstd[:, None]  # (d, 1) constant columns
    s1, s2 = fused_moment_sums(
        xr[:tile], xr, mu[:tile], mu, rstd[:tile], rstd, c[:tile],
        m_total=m, bi=8, bj=16, bm=256, interpret=True,
    )
    atol = 2e-6 if dtype == np.float32 else 5e-2
    # mask the degenerate self-pair entries (i, i) of the (tile, d) slab
    mask = 1.0 - jnp.eye(tile, d)
    np.testing.assert_allclose(
        np.asarray(m1r[:tile] * m * mask), np.asarray(s1 * mask),
        atol=atol * m, rtol=0,
    )
    np.testing.assert_allclose(
        np.asarray(m2r[:tile] * m * mask), np.asarray(s2 * mask),
        atol=atol * m, rtol=0,
    )


# Every block a Pallas call gets from the shape rules must tile the way
# the TPU requires: last dimension a multiple of 128 or the whole array
# extent, second-to-last a multiple of 8 or the whole extent. Checked on
# the traced pallas_call at every d up to 130 (staged compaction shrinks
# the width through all of them) and at the paper's widths.
_WIDTHS = list(range(1, 131)) + [200, 487, 964, 1000]


def _subjaxprs(params):
    for v in params.values():
        for item in v if isinstance(v, (list, tuple)) else (v,):
            sub = getattr(item, "jaxpr", item)
            if hasattr(sub, "eqns"):
                yield sub


def _pallas_blocks(fn, *shapes):
    """(block shape, array shape) of every pallas_call operand in fn."""
    import jax

    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                for bm in eqn.params["grid_mapping"].block_mappings:
                    found.append((
                        tuple(getattr(b, "block_size", b)
                              for b in bm.block_shape),
                        tuple(bm.array_aval.shape),
                    ))
            for sub in _subjaxprs(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*[
        jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes
    ]).jaxpr)
    return found


_BLOCK_CASES = {
    "pair": lambda d: (
        lambda x, c: ops.pairwise_moments(x, c, backend="pallas"),
        (700, d), (d, d)),
    "chunked": lambda d: (
        lambda x, c: ops.pairwise_moments_chunked(
            x, c, chunk=256, backend="pallas"),
        (700, d), (d, d)),
    "rows_half_tile": lambda d: (
        lambda x, c: ops.pairwise_moment_sums_rows(
            x, c, d - (d + 1) // 2, (d + 1) // 2, chunk=256,
            backend="pallas"),
        (512, d), (d, d)),
    "fused": lambda d: (
        lambda x, mu, rstd, c: ops.fused_moment_rows(
            x, mu, rstd, c, 0, min(d, 8)),
        (700, d), (d,), (d,), (d, d)),
}


@pytest.mark.parametrize("case", sorted(_BLOCK_CASES))
def test_pallas_blocks_tile_legally_at_every_width(case):
    for d in _WIDTHS:
        fn, *shapes = _BLOCK_CASES[case](d)
        blocks = _pallas_blocks(fn, *shapes)
        assert blocks, (case, d)
        for block, array in blocks:
            assert block[-1] % 128 == 0 or block[-1] == array[-1], (
                case, d, block, array)
            assert block[-2] % 8 == 0 or block[-2] == array[-2], (
                case, d, block, array)


# ---------------------------------------------------------------------------
# Block plans: a function of the shapes
# ---------------------------------------------------------------------------


def test_heuristic_matches_legacy_pick_blocks():
    """The pair-tile rule keeps the old ops._pick_blocks rows and sample
    blocks; below 128 columns bj is the whole padded column extent, the
    only sub-lane block the TPU accepts."""
    from repro.kernels.tune import registry

    legacy = {
        (300, 4): (8, 8, 256),
        (300, 16): (8, 16, 256),
        (600, 64): (8, 64, 512),
        (600, 128): (8, 128, 512),
        (5000, 200): (8, 128, 2048),
    }
    for (m, d), want in legacy.items():
        assert registry.heuristic_pair_blocks(d, m) == want, (m, d)


def test_default_interpret_tracks_backend():
    """interpret=None resolves from the detected backend: the Pallas
    interpreter only when no accelerator backs the process."""
    import jax

    from repro.kernels.tune import registry

    expect = jax.default_backend() == "cpu"
    assert registry.default_interpret() is expect
    assert registry.resolve_interpret(None) is expect
    assert registry.resolve_interpret(True) is True
    assert registry.resolve_interpret(False) is False


def test_unknown_backend_raises():
    xs, c = _make(64, 8)
    with pytest.raises(ValueError, match="unknown backend"):
        ops.pairwise_moments(xs, c, backend="bogus")
    with pytest.raises(ValueError, match="unknown backend"):
        ops.pairwise_moments_chunked(xs, c, chunk=32, backend="bogus")
    with pytest.raises(ValueError, match="row tile"):
        ops.pairwise_moment_sums_rows(xs, c, 0, 8, backend="ref")


# Any blocks give bit-identical moments: bi/bj only re-tile the pair space,
# and bm is accumulated in fixed ACCUM_CHUNK sub-sums. Each case is checked
# against the blocks the shape rule picks: (8, 24, 512) for the pair tile at
# 700 x 24, row block 24 for the blocked path, (8, 16, 512) for the row tile.
@pytest.mark.parametrize(
    "blocks", [(8, 24, 128), (16, 24, 256), (32, 24, 512), (64, 24, 128)]
)
def test_pair_op_parity_bit_identical_across_plans(blocks):
    xs, c = _make(700, 24)
    ref1, ref2 = ops.pairwise_moments(
        xs, c, backend="pallas", interpret=True
    )
    m1, m2 = ops.pairwise_moments(
        xs, c, backend="pallas", interpret=True, blocks=blocks
    )
    assert np.array_equal(np.asarray(ref1), np.asarray(m1))
    assert np.array_equal(np.asarray(ref2), np.asarray(m2))


@pytest.mark.parametrize("block", [8, 16])
def test_blocked_parity_bit_identical_across_blocks(block):
    import jax

    xs, c = _make(700, 24)
    ref1, ref2 = ops.pairwise_moments(xs, c, backend="blocked")
    m1, m2 = jax.jit(ops.pairwise_moments_blocked, static_argnames="block")(
        xs, c, block=block)
    assert np.array_equal(np.asarray(ref1), np.asarray(m1))
    assert np.array_equal(np.asarray(ref2), np.asarray(m2))


@pytest.mark.parametrize(
    "blocks", [(16, 16, 128), (32, 16, 256), (8, 16, 256)]
)
def test_rows_op_parity_bit_identical_across_plans(blocks):
    xs, c = _make(512, 16)
    r1, r2 = ops.pairwise_moment_sums_rows(
        xs, c, 0, 16, chunk=512, backend="pallas", interpret=True,
    )
    s1, s2 = ops.pairwise_moment_sums_rows(
        xs, c, 0, 16, chunk=512, backend="pallas", interpret=True,
        blocks=blocks,
    )
    assert np.array_equal(np.asarray(r1), np.asarray(s1))
    assert np.array_equal(np.asarray(r2), np.asarray(s2))


def _pallas_plan(fn, *shapes):
    """(grid, (bi, bj, bm), (d_pad, m_pad)) of fn's one pallas_call, read
    from its x_i and x_j blocks."""
    import jax

    plans = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                x_i, x_j = gm.block_mappings[:2]
                (bi, bm), (bj, _) = (
                    tuple(getattr(b, "block_size", b) for b in bmap.block_shape)
                    for bmap in (x_i, x_j)
                )
                plans.append((tuple(gm.grid), (bi, bj, bm),
                              tuple(x_i.array_aval.shape)))
            for sub in _subjaxprs(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*[
        jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes
    ]).jaxpr)
    (plan,) = plans
    return plan


# The pair tile at every stage width of the benchmark cells' staged fits:
# (width, grid, (bi, bj, bm), (d_pad, m_pad)). Rows pad to 8 and columns
# to 128 above one lane tile; at most 128 columns are one block.
_GENE964_STAGES = [
    (964, (121, 8, 2), (8, 128, 2048), (1024, 4096)),
    (723, (91, 6, 2), (8, 128, 2048), (768, 4096)),
    (542, (68, 5, 2), (8, 128, 2048), (640, 4096)),
    (406, (51, 4, 2), (8, 128, 2048), (512, 4096)),
    (304, (38, 3, 2), (8, 128, 2048), (384, 4096)),
    (228, (29, 2, 2), (8, 128, 2048), (256, 4096)),
    (171, (22, 2, 2), (8, 128, 2048), (256, 4096)),
    (128, (16, 1, 2), (8, 128, 2048), (128, 4096)),
    (96, (12, 1, 2), (8, 96, 2048), (96, 4096)),
    (72, (9, 1, 2), (8, 72, 2048), (72, 4096)),
    (54, (7, 1, 2), (8, 56, 2048), (56, 4096)),
    (40, (5, 1, 2), (8, 40, 2048), (40, 4096)),
    (30, (4, 1, 2), (8, 32, 2048), (32, 4096)),
    (22, (3, 1, 2), (8, 24, 2048), (24, 4096)),
    (16, (2, 1, 2), (8, 16, 2048), (16, 4096)),
    (12, (2, 1, 2), (8, 16, 2048), (16, 4096)),
    (9, (2, 1, 2), (8, 16, 2048), (16, 4096)),
    (7, (1, 1, 2), (8, 8, 2048), (8, 4096)),
]
_STOCKS487_STAGES = [
    (487, (61, 4, 8), (8, 128, 512), (512, 4096)),
    (365, (46, 3, 8), (8, 128, 512), (384, 4096)),
    (274, (35, 3, 8), (8, 128, 512), (384, 4096)),
    (206, (26, 2, 8), (8, 128, 512), (256, 4096)),
    (154, (20, 2, 8), (8, 128, 512), (256, 4096)),
    (116, (15, 1, 8), (8, 120, 512), (120, 4096)),
    (87, (11, 1, 8), (8, 88, 512), (88, 4096)),
    (65, (9, 1, 8), (8, 72, 512), (72, 4096)),
    (49, (7, 1, 8), (8, 56, 512), (56, 4096)),
    (37, (5, 1, 8), (8, 40, 512), (40, 4096)),
    (28, (4, 1, 8), (8, 32, 512), (32, 4096)),
    (21, (3, 1, 8), (8, 24, 512), (24, 4096)),
    (16, (2, 1, 8), (8, 16, 512), (16, 4096)),
    (12, (2, 1, 8), (8, 16, 512), (16, 4096)),
    (9, (2, 1, 8), (8, 16, 512), (16, 4096)),
    (7, (1, 1, 8), (8, 8, 512), (8, 4096)),
]
_CELL_STAGES = {
    "gene964": (4096, 964, False, _GENE964_STAGES),
    "stocks487": (3999, 487, False, _STOCKS487_STAGES),
    "gene964int": (4096, 964, True, _GENE964_STAGES),
}


@pytest.mark.parametrize("cell", sorted(_CELL_STAGES))
def test_block_plans_at_cell_stage_widths(cell):
    """The Pallas grid, blocks and padded extents the shape rules give the
    benchmark cells' kernels at every stage width of the staged fit."""
    from repro.core import ordering

    m, d, masked, stages = _CELL_STAGES[cell]
    widths = [w for w, _ in ordering._stage_schedule(d)]
    assert widths == [w for w, *_ in stages]
    for w, *want in stages:
        if masked:
            def fn(z, v, c, a, g, n):
                return ops.pairwise_moments_masked(
                    z, v, c, a, g, n, backend="pallas", interpret=False)
            shapes = [(m, w), (m, w)] + [(w, w)] * 4
        else:
            def fn(x, c):
                return ops.pairwise_moments(
                    x, c, backend="pallas", interpret=False)
            shapes = [(m, w), (w, w)]
        assert _pallas_plan(fn, *shapes) == tuple(want), (cell, w)


def test_kernel_ops_import_loads_no_obs():
    """The kernel layer stands below observability: importing it loads no
    ``repro.obs`` module."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ, PYTHONPATH=os.path.abspath(src),
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, repro.kernels.ops; "
         "print(sorted(m for m in sys.modules if m.startswith('repro.')))"],
        env=env, capture_output=True, text=True, check=True,
    ).stdout
    assert "repro.kernels.ops" in out
    assert "repro.obs" not in out


def test_engine_warmup_compiles():
    from repro.core import api
    from repro.obs import compile_log
    from repro.serve.engine import CausalDiscoveryEngine, FitRequest

    eng = CausalDiscoveryEngine(
        api.FitConfig(backend="blocked", compaction="staged", min_stage=3)
    )
    n0 = compile_log.total("batched.fit_many")
    assert eng.warmup([(64, 5)]) is None
    # Warmup pre-compiled the vmap fit program (public compile-log pin:
    # one event per (shape, config) signature).
    n_warm = compile_log.total("batched.fit_many")
    assert n_warm == n0 + 1
    x = RNG.laplace(size=(64, 5)).astype(np.float32)
    (req,) = eng.run([FitRequest(data=x)])
    assert sorted(req.result.order.tolist()) == list(range(5))
    # Steady state: the warmed shape serves with zero new compiles.
    assert compile_log.total("batched.fit_many") == n_warm
