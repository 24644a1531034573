"""LayoutTable (flat-slab client state) round-trip and contract tests.

The slab engine's correctness rests on three properties of
:class:`repro.core.flat.LayoutTable` (the "layout-table contract"):

  * ``ravel → unravel`` is bit-exact for any pytree (including zero-size
    leaves, scalars, and widths that are not 128-multiples) under any
    leading shape — ``()``, ``(c,)``, ``(m, c)``;
  * the ``dim_aligned − dim`` tail columns of a ravelled matrix are
    exactly zero (column-independent mixes then can't see them);
  * ``unravel`` restores each leaf's template dtype and raises on a
    matrix narrower than the layout (slab/template mismatch).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, load_ci_profile, st
from repro.core import flat
from repro.kernels import ops

load_ci_profile(max_examples=25)


def _tree_from_shapes(shapes, seed=0, dtypes=None):
    rng = np.random.default_rng(seed)
    dtypes = dtypes or [jnp.float32] * len(shapes)
    return {
        f"leaf{i:02d}": jnp.asarray(
            rng.normal(size=s).astype(np.float32)).astype(dt)
        for i, (s, dt) in enumerate(zip(shapes, dtypes))
    }


SHAPE_SETS = [
    [(4, 3), (7,), (2, 2, 2)],          # generic multi-leaf
    [(97,)],                            # non-128-multiple width
    [(128,), (128, 2)],                 # exact lane multiples
    [(0, 3), (5,)],                     # zero-size leaf
    [(), (3,)],                         # scalar leaf
    [(1,)],                             # minimal
]


@pytest.mark.parametrize("shapes", SHAPE_SETS)
@pytest.mark.parametrize("lead", [(), (3,), (2, 4)])
def test_ravel_unravel_roundtrip(shapes, lead):
    tree = _tree_from_shapes(shapes)
    layout = flat.LayoutTable.build(tree)
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, lead + x.shape) + 0.0, tree)
    mat = layout.ravel(stacked)
    assert mat.shape == lead + (layout.dim_aligned,)
    assert mat.dtype == jnp.float32
    back = layout.unravel(mat)
    for a, b in zip(jax.tree.leaves(stacked), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("shapes", SHAPE_SETS)
def test_alignment_and_zero_tail(shapes):
    tree = _tree_from_shapes(shapes)
    layout = flat.LayoutTable.build(tree)
    assert layout.dim == sum(int(np.prod(s)) for s in shapes)
    assert layout.dim_aligned == ops.aligned_dim(layout.dim)
    assert layout.dim_aligned % ops.ALIGN == 0 or layout.dim_aligned == 0
    mat = np.asarray(layout.ravel(tree))
    np.testing.assert_array_equal(mat[layout.dim:], 0.0)


def test_unravel_restores_dtypes_exactly():
    # bf16 -> f32 widening is exact, so the round-trip must be too
    tree = _tree_from_shapes([(6, 2), (9,)],
                             dtypes=[jnp.bfloat16, jnp.float32])
    layout = flat.LayoutTable.build(tree)
    back = layout.unravel(layout.ravel(tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_unravel_ignores_tail_garbage():
    # unravel only reads the first `dim` columns: junk in the aligned
    # tail (e.g. a transport EF slab reused as scratch) must not leak
    tree = _tree_from_shapes([(5, 3), (7,)])
    layout = flat.LayoutTable.build(tree)
    mat = layout.ravel(tree)
    junk = mat.at[..., layout.dim:].set(123.0)
    for a, b in zip(jax.tree.leaves(tree),
                    jax.tree.leaves(layout.unravel(junk))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unravel_too_narrow_raises():
    layout = flat.LayoutTable.build(_tree_from_shapes([(10,)]))
    with pytest.raises(ValueError, match="different template"):
        layout.unravel(jnp.zeros((3, 4)))


def test_build_empty_tree_raises():
    with pytest.raises(ValueError, match="empty params tree"):
        flat.LayoutTable.build({})


def test_slab_broadcast():
    tree = _tree_from_shapes([(4, 3), (5,)])
    layout = flat.LayoutTable.build(tree)
    slab = layout.slab(tree, 6)
    assert slab.shape == (6, layout.dim_aligned)
    vec = np.asarray(layout.ravel(tree))
    for row in np.asarray(slab):
        np.testing.assert_array_equal(row, vec)


@given(
    shapes=st.lists(
        st.lists(st.integers(min_value=0, max_value=5),
                 min_size=0, max_size=3),
        min_size=1, max_size=5),
    lead=st.sampled_from([(), (2,), (3, 2)]),
)
def test_roundtrip_property(shapes, lead):
    tree = _tree_from_shapes([tuple(s) for s in shapes], seed=1)
    layout = flat.LayoutTable.build(tree)
    stacked = jax.tree.map(
        lambda x: jnp.broadcast_to(x, lead + x.shape) + 0.0, tree)
    mat = layout.ravel(stacked)
    assert mat.shape == lead + (layout.dim_aligned,)
    np.testing.assert_array_equal(
        np.asarray(mat)[..., layout.dim:], 0.0)
    for a, b in zip(jax.tree.leaves(stacked),
                    jax.tree.leaves(layout.unravel(mat))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
