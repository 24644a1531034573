"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, load_ci_profile, st
from repro.kernels import ops, ref

load_ci_profile(max_examples=20, suppress_too_slow=True)


SHAPES_MIX = [(1, 1, 128), (4, 8, 300), (16, 16, 1024), (5, 7, 97),
              (32, 32, 2048), (3, 20, 513)]


@pytest.mark.parametrize("k,m,d", SHAPES_MIX)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mix_aggregate_matches_oracle(k, m, d, dtype):
    rng = np.random.default_rng(k * 100 + m)
    w = jnp.asarray(rng.normal(size=(k, m)).astype(np.float32))
    t = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32)).astype(dtype)
    got = ops.mix_aggregate(w, t, impl="interpret")
    want = ref.mix_aggregate(w, t)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol)
    assert got.dtype == t.dtype


@pytest.mark.parametrize("block_d", [128, 256, 2048])
def test_mix_aggregate_block_sweep(block_d):
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(6, 6)).astype(np.float32))
    t = jnp.asarray(rng.normal(size=(6, 777)).astype(np.float32))
    got = ops.mix_aggregate(w, t, impl="interpret", block_d=block_d)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.mix_aggregate(w, t)),
                               rtol=1e-5, atol=1e-5)


SHAPES_SCATTER = [(8, 3, 128), (16, 6, 300), (9, 4, 513), (32, 5, 2048),
                  (8, 8, 777)]


def _scatter_case(m, c, d, pads, rng):
    w = jnp.asarray(rng.normal(size=(c, c)).astype(np.float32))
    if pads:
        w = w * jnp.asarray(np.arange(c) < c - pads, np.float32)[None, :]
    theta = jnp.asarray(rng.normal(size=(c, d)).astype(np.float32))
    full = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    real = np.sort(rng.choice(m, size=c - pads, replace=False))
    idx = jnp.asarray(np.concatenate([real, [m] * pads]).astype(np.int32))
    mask = jnp.asarray(np.arange(c) < c - pads)
    return w, theta, idx, mask, full, real


@pytest.mark.parametrize("m,c,d", SHAPES_SCATTER)
@pytest.mark.parametrize("pads", [0, 2])
def test_masked_mix_scatter_matches_oracle(m, c, d, pads):
    if pads >= c:
        pytest.skip("needs at least one real slot")
    rng = np.random.default_rng(m * 100 + c + pads)
    w, theta, idx, mask, full, real = _scatter_case(m, c, d, pads, rng)
    # ref first: the pallas path donates `full` on backends that support
    # buffer donation
    want = ref.masked_mix_scatter(w, theta, idx, mask, full)
    got = ops.masked_mix_scatter(w, theta, idx, mask, jnp.array(full),
                                 impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_masked_mix_scatter_untouched_rows_identical():
    """Rows outside the cohort never move — bit-identical, not just close."""
    rng = np.random.default_rng(0)
    m, c, d = 16, 5, 300
    w, theta, idx, mask, full, real = _scatter_case(m, c, d, 1, rng)
    before = np.asarray(full).copy()
    out = np.asarray(ops.masked_mix_scatter(w, theta, idx, mask,
                                            jnp.array(full),
                                            impl="interpret"))
    absent = np.setdiff1d(np.arange(m), real)
    np.testing.assert_array_equal(out[absent], before[absent])
    assert np.abs(out[real] - before[real]).max() > 0


def test_masked_mix_scatter_equals_mix_then_scatter():
    """The fusion must equal mix_aggregate + row scatter on real slots."""
    rng = np.random.default_rng(3)
    m, c, d = 12, 4, 257
    w, theta, idx, mask, full, real = _scatter_case(m, c, d, 0, rng)
    mixed = np.asarray(ref.mix_aggregate(w, theta))
    want = np.asarray(full).copy()
    want[real] = mixed
    got = np.asarray(ref.masked_mix_scatter(w, theta, idx, mask, full))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ------------------------------------------- HBM-resident cohort variant
#
# CI's multi-device job re-runs this file under 8 forced host devices, so
# the interpret-mode kernels are exercised at both 1 and 8 devices.

SHAPES_HBM = [(8, 3, 128), (16, 6, 300), (9, 4, 513), (32, 5, 2048),
              (8, 8, 777), (4, 6, 128)]  # (4, 6, ·): c > m


@pytest.mark.parametrize("m,c,d", SHAPES_HBM)
@pytest.mark.parametrize("pads", [0, 2])
def test_hbm_mix_scatter_matches_slab_and_oracle(m, c, d, pads):
    if c - pads > m:
        pytest.skip("more real slots than clients")
    rng = np.random.default_rng(m * 1000 + c * 10 + pads)
    w, theta, idx, mask, full, real = _scatter_case(m, c, d, pads, rng)
    want = ref.masked_mix_scatter(w, theta, idx, mask, full)
    slab = ops.masked_mix_scatter(w, theta, idx, mask, jnp.array(full),
                                  impl="interpret_slab")
    got = ops.masked_mix_scatter(w, theta, idx, mask, jnp.array(full),
                                 impl="interpret_hbm")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(slab),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("variant", ["interpret_slab", "interpret_hbm"])
def test_mix_scatter_all_pad_cohort_is_identity(variant):
    """A sentinel-only cohort (every slot padded) must not move a byte."""
    m, c, d = 10, 4, 257
    rng = np.random.default_rng(7)
    w, theta, idx, mask, full, _ = _scatter_case(m, c, d, c, rng)
    assert not np.asarray(mask).any()
    out = ops.masked_mix_scatter(w, theta, idx, mask, jnp.array(full),
                                 impl=variant)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(full))


def test_hbm_mix_scatter_untouched_rows_identical():
    """The HBM row path never writes a non-cohort row — bit-identical."""
    rng = np.random.default_rng(1)
    m, c, d = 16, 5, 300
    w, theta, idx, mask, full, real = _scatter_case(m, c, d, 1, rng)
    before = np.asarray(full).copy()
    out = np.asarray(ops.masked_mix_scatter(w, theta, idx, mask,
                                            jnp.array(full),
                                            impl="interpret_hbm"))
    absent = np.setdiff1d(np.arange(m), real)
    np.testing.assert_array_equal(out[absent], before[absent])
    assert np.abs(out[real] - before[real]).max() > 0


@pytest.mark.parametrize("m,c,d", [(8, 3, 128), (9, 4, 513), (4, 6, 300)])
def test_cohort_gather_matches_take(m, c, d):
    """The row-loop gather is bit-identical to clamped jnp.take,
    including pad sentinels (>= m) and duplicate indices."""
    rng = np.random.default_rng(m + c)
    full = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    idx = jnp.asarray(
        np.concatenate([rng.integers(0, m, size=c - 2), [0, m]]), jnp.int32)
    got = ops.cohort_gather(full, idx, impl="interpret")
    want = ref.cohort_gather(full, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(want),
        np.asarray(jnp.take(full, jnp.minimum(idx, m - 1), axis=0)))


def test_kernel_shape_contracts_raise():
    """Both scatter variants reject malformed shapes with ValueError (not
    assert — the contract must survive python -O)."""
    from repro.kernels.cohort_rows import gather_rows, mix_scatter_rows
    from repro.kernels.masked_mix_scatter import masked_mix_scatter_pallas

    w = jnp.zeros((3, 3))
    theta = jnp.zeros((3, 16))
    full = jnp.zeros((8, 16))
    idx = jnp.zeros((3,), jnp.int32)
    mask = jnp.ones((3,), bool)
    for kernel in (functools.partial(masked_mix_scatter_pallas,
                                     interpret=True), mix_scatter_rows):
        with pytest.raises(ValueError):
            kernel(jnp.zeros((3, 2)), theta, idx, mask, jnp.array(full))
        with pytest.raises(ValueError):
            kernel(w, jnp.zeros((3, 8)), idx, mask, jnp.array(full))
        with pytest.raises(ValueError):
            kernel(w, theta, jnp.zeros((4,), jnp.int32), mask,
                   jnp.array(full))
        with pytest.raises(ValueError):
            kernel(w, theta, idx, mask, jnp.zeros((8, 16, 1)))
    with pytest.raises(ValueError):
        gather_rows(jnp.zeros((8,)), idx)
    with pytest.raises(ValueError):
        gather_rows(full, jnp.zeros((3, 1), jnp.int32))


def test_aligned_dim_and_zero_copy_bound():
    """aligned_dim rounds to the 128 lane multiple, and state created at
    that width (8-multiple rows) takes the slab kernel's aliased
    zero-copy path — no O(m·d) padding copy."""
    from repro.kernels.masked_mix_scatter import padding_copy_needed

    assert ops.aligned_dim(1) == 128
    assert ops.aligned_dim(128) == 128
    assert ops.aligned_dim(129) == 256
    assert padding_copy_needed(8, 3, 300)  # unaligned d forces the copy
    assert not padding_copy_needed(8, 3, ops.aligned_dim(300))


def test_lenet_label_shift_buffer_takes_zero_copy_path():
    """Regression for the aligned-width satellite: the LeNet/label-shift
    bench config's flat upload width, created at ``ops.aligned_dim``
    (as ``async_buffer.init_buffer`` now does), never needs the O(m·d)
    zero-pad copy — the aliased kernel path always applies."""
    from repro.kernels.masked_mix_scatter import padding_copy_needed
    from repro.models import lenet

    params0 = lenet.init(jax.random.PRNGKey(0), input_hw=(16, 16),
                         channels=1, num_classes=8)
    d = sum(x.size for x in jax.tree.leaves(params0))
    assert padding_copy_needed(8, 4, d)  # the raw LeNet dim is unaligned
    assert not padding_copy_needed(8, 4, ops.aligned_dim(d))


@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 24),
       c=st.integers(1, 8), d=st.integers(1, 300), pads=st.integers(0, 8),
       hbm=st.booleans())
def test_mix_scatter_noncohort_rows_property(seed, m, c, d, pads, hbm):
    """Both kernel variants leave non-cohort rows bit-identical and match
    the oracle on cohort rows — any shape, any pad count (including the
    all-pad cohort), c > m allowed."""
    pads = min(pads, c)
    if c - pads > m:
        pads = c - m
    rng = np.random.default_rng(seed)
    w, theta, idx, mask, full, real = _scatter_case(m, c, d, pads, rng)
    impl = "interpret_hbm" if hbm else "interpret_slab"
    out = np.asarray(ops.masked_mix_scatter(w, theta, idx, mask,
                                            jnp.array(full), impl=impl))
    absent = np.setdiff1d(np.arange(m), real)
    np.testing.assert_array_equal(out[absent], np.asarray(full)[absent])
    want = np.asarray(ref.masked_mix_scatter(w, theta, idx, mask, full))
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)


@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 12),
       c=st.integers(1, 6), d=st.integers(2, 100), pads=st.integers(0, 6))
def test_mix_scatter_flat_property(seed, m, c, d, pads):
    """aggregation.mix_scatter_flat leaves non-cohort rows bit-identical
    on the single-leaf slab state, and a wider flat_c (tail columns past
    the state dim, even garbage) changes nothing."""
    from repro.core import aggregation

    pads = min(pads, c)
    if c - pads > m:
        pads = c - m
    rng = np.random.default_rng(seed)
    w, theta, idx, mask, full, real = _scatter_case(m, c, d, pads, rng)
    tree = {"slab": jnp.asarray(full)}
    out = aggregation.mix_scatter_flat(tree, theta, w, idx, mask,
                                       impl="ref")
    wide = jnp.concatenate(
        [theta, jnp.full((c, ops.aligned_dim(d) + 128 - d), 99.0)],
        axis=1)
    out_wide = aggregation.mix_scatter_flat(tree, wide, w, idx, mask,
                                            impl="ref")
    absent = np.setdiff1d(np.arange(m), real)
    a, b = np.asarray(out["slab"]), np.asarray(out_wide["slab"])
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a[absent], np.asarray(full)[absent])


def test_mix_scatter_multi_leaf_state_raises():
    """The slab engine is the contract: a multi-leaf stacked state on the
    mix path is a caller error, not a fallback."""
    from repro.core import aggregation

    rng = np.random.default_rng(0)
    w, theta, idx, mask, full, _ = _scatter_case(6, 3, 10, 0, rng)
    tree = {"a": full[:, :5], "b": full[:, 5:]}
    with pytest.raises(ValueError, match="multi-leaf stacked state"):
        aggregation.mix_scatter_flat(tree, theta, w, idx, mask, impl="ref")
    with pytest.raises(ValueError, match="multi-leaf stacked state"):
        aggregation.mix_scatter(
            tree, {"a": theta[:, :5], "b": theta[:, 5:]}, w, idx, mask,
            impl="ref")


def test_masked_mix_scatter_width_mismatch_raises():
    """ops.masked_mix_scatter refuses an upload whose width disagrees
    with the state slab (a layout-table/slab mismatch), as a ValueError
    rather than a kernel-shape assert."""
    rng = np.random.default_rng(1)
    w, theta, idx, mask, full, _ = _scatter_case(6, 3, 10, 0, rng)
    with pytest.raises(ValueError, match="layout table and the slab"):
        ops.masked_mix_scatter(w, theta[:, :6], idx, mask,
                               jnp.array(full), impl="ref")


@pytest.mark.parametrize("m,d", [(2, 64), (8, 500), (16, 4096), (9, 129)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pairwise_delta_matches_oracle(m, d, dtype):
    rng = np.random.default_rng(m)
    g = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32)).astype(dtype)
    got = ops.pairwise_delta(g, impl="interpret")
    want = ref.pairwise_delta(g)
    tol = 1e-3 * d if dtype == jnp.bfloat16 else 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=tol)


@pytest.mark.parametrize("m", [20, 300])
@pytest.mark.parametrize("d", [1000, 5000])
def test_gram_tiled_matches_oracle(m, d):
    """The smallest output tiles (128: a block_m under it rounds up), so
    that m=20 takes the two-input path with one ragged tile and m=300
    takes 3 x 3 tiles with ragged edges; d blocks of 256 with a ragged
    last block."""
    from repro.kernels.pairwise_delta import gram_pallas, tiles

    tile_m, tile_d, inputs = tiles(m, d, block_m=8, block_d=256)
    assert (tile_m, tile_d, inputs) == (128, 256, 2)
    assert m % tile_m and d % tile_d
    rng = np.random.default_rng(m + d)
    g = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    got = np.asarray(gram_pallas(g, block_m=8, block_d=256,
                                 interpret=True))
    want = np.asarray(ref.gram(g))
    # a float32 dot product's round-off scales with sum |g_ik g_jk|, which
    # the diagonal bounds, not with the (possibly tiny) entry itself
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(np.max(np.diag(want))))


def test_gram_tiles_keep_one_block_until_the_tile_is_full():
    """The paper shape is one output block through one input, as before
    the tiling; FEMNIST's 3,550 clients take 7 x 7 tiles of 512 within
    the VMEM budget."""
    from repro.kernels.masked_mix_scatter import _VMEM_BUDGET_FLOATS
    from repro.kernels.pairwise_delta import tiles

    assert tiles(20, 45276) == (24, 4096, 1)
    tile_m, tile_d, inputs = tiles(3550, 48846)
    assert (tile_m, inputs) == (512, 2) and -(-3550 // tile_m) == 7
    assert (2 * inputs * tile_m * tile_d + 2 * tile_m * tile_m
            <= _VMEM_BUDGET_FLOATS)


@pytest.mark.parametrize("m,k,f", [(4, 2, 4), (20, 4, 20), (100, 7, 100),
                                   (9, 3, 17)])
def test_kmeans_assign_matches_oracle(m, k, f):
    rng = np.random.default_rng(m + k)
    p = jnp.asarray(rng.normal(size=(m, f)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(k, f)).astype(np.float32))
    l1, d1 = ops.kmeans_assign(p, c, impl="interpret")
    l2, d2 = ref.kmeans_assign(p, c)
    assert np.array_equal(np.asarray(l1), np.asarray(l2))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-4, atol=1e-4)


@given(
    m=st.integers(2, 12), d=st.integers(1, 200),
    seed=st.integers(0, 2**31 - 1),
)
def test_pairwise_delta_properties(m, d, seed):
    rng = np.random.default_rng(seed)
    g = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    delta = np.asarray(ops.pairwise_delta(g, impl="interpret"))
    assert delta.shape == (m, m)
    # symmetric, nonnegative, zero diagonal
    np.testing.assert_allclose(delta, delta.T, rtol=1e-4, atol=1e-4)
    assert (delta >= 0).all()
    np.testing.assert_allclose(np.diag(delta), 0.0, atol=1e-3 * d)


@given(
    k=st.integers(1, 8), m=st.integers(1, 8), seed=st.integers(0, 2**31 - 1)
)
def test_mix_aggregate_linearity(k, m, seed):
    """Mixing is linear: mix(W, a+b) == mix(W, a) + mix(W, b)."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.normal(size=(k, m)).astype(np.float32))
    a = jnp.asarray(rng.normal(size=(m, 130)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(m, 130)).astype(np.float32))
    lhs = ops.mix_aggregate(w, a + b, impl="interpret")
    rhs = (ops.mix_aggregate(w, a, impl="interpret")
           + ops.mix_aggregate(w, b, impl="interpret"))
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-4, atol=1e-4)


def test_mix_aggregate_row_stochastic_preserves_constant():
    """A row-stochastic W maps constant models to the same constant."""
    m = 8
    w = jnp.ones((m, m)) / m
    t = jnp.full((m, 257), 3.25, jnp.float32)
    out = ops.mix_aggregate(w, t, impl="interpret")
    np.testing.assert_allclose(np.asarray(out), 3.25, rtol=1e-6)
