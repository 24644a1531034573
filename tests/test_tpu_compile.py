"""Compile the main path's kernels for a described TPU v5e, at real shapes.

Nothing runs: the TPU compiler, which is installed alongside JAX, lowers
and compiles each kernel for a chip that is described, not attached. It
refuses what interpret mode accepts: slices not aligned to the (8, 128)
tiling, and kernels whose buffers overflow the scoped VMEM limit. The
shapes are the paper-scale round (m=20 clients, LeNet at 28x28 with 20
classes: d=45,312 lane-aligned), the large-m regime (m=16,384, c=64) and
FEMNIST's Eq. 9 init (m=3,550, 62 classes: d=48,846).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so describing it while test modules are
collected would fail in every other test worker.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.masked_mix_scatter import slab_fits

D = 45312  # LeNet 28x28, 20 classes, aligned to 128 lanes
M_LARGE, C_LARGE = 16384, 64
M_FEMNIST, D_FEMNIST = 3550, 48846  # LEAF FEMNIST writers; LeNet, 62 classes
F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=F32: jax.ShapeDtypeStruct(shape, dtype,
                                                         sharding=one_chip)


def _compile(fn, *args, donate=()):
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile()


def _mix_scatter(spec, m, c, impl):
    return _compile(
        lambda w, t, i, k, f: ops.masked_mix_scatter(w, t, i, k, f,
                                                     impl=impl),
        spec((c, c)), spec((c, D)), spec((c,), I32), spec((c,), jnp.bool_),
        spec((m, D)), donate=(4,))


def _slab_bound(c):
    m = 8
    while slab_fits(m + 8, c):
        m += 8
    return m


@pytest.mark.parametrize("m,c", [(20, 10), (24, 10)])
def test_slab_kernel_compiles_at_paper_scale(spec, m, c):
    compiled = _mix_scatter(spec, m, c, "pallas_slab")
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("c", [10, 64, 256])
def test_slab_kernel_compiles_at_slab_fits_bound(spec, c):
    """Every (m, c) that slab_fits accepts compiles: its largest m does."""
    m = _slab_bound(c)
    assert slab_fits(m, c) and not slab_fits(m + 8, c)
    compiled = _mix_scatter(spec, m, c, "pallas_slab")
    assert "tpu_custom_call" in compiled.as_text()


def _assert_no_state_copy(compiled, m):
    """No temporary and no copy the size of the (m, D) state."""
    state_bytes = m * D * 4
    assert compiled.memory_analysis().temp_size_in_bytes < state_bytes // 100
    assert f"f32[{m},{D}]" not in "\n".join(
        ln for ln in compiled.as_text().splitlines()
        if " copy(" in ln or "copy-start(" in ln)


def test_hbm_cohort_gather_moves_only_cohort_rows(spec):
    compiled = _compile(lambda f, i: ops.cohort_gather(f, i, impl="pallas"),
                        spec((M_LARGE, D)), spec((C_LARGE,), I32))
    _assert_no_state_copy(compiled, M_LARGE)


def test_hbm_mix_scatter_moves_only_cohort_rows(spec):
    compiled = _mix_scatter(spec, M_LARGE, C_LARGE, "pallas_hbm")
    _assert_no_state_copy(compiled, M_LARGE)
    assert compiled.memory_analysis().alias_size_in_bytes == M_LARGE * D * 4


def test_auto_variant_switches_to_hbm_past_slab_fits():
    assert slab_fits(20, 10) and not slab_fits(M_LARGE, C_LARGE)


@pytest.mark.parametrize("m,d", [(20, D), (1024, D), (M_FEMNIST, D_FEMNIST),
                                 (20, 45276)],
                         ids=["20", "1024", "3550", "20-unaligned"])
def test_gram_kernel_compiles(spec, m, d):
    """Up to FEMNIST's 3,550 clients, whose (3,552)^2 f32 Gram is more
    than the scoped VMEM as one block: the output is tiled."""
    compiled = _compile(lambda g: ops.pairwise_delta(g, impl="pallas"),
                        spec((m, d)))
    assert "tpu_custom_call" in compiled.as_text()


def test_special_round_fits_at_femnist_scale(spec):
    """The Eq. 9 gradient program at FEMNIST scale (3,550 writers of 204
    samples, Eq. 10 minibatch 50, 62 classes) with the derived client
    chunk: arguments, output and temporaries under 12 GB of the chip's 16.
    Unchunked, one conv1 activation buffer alone needs 25 GB."""
    from repro.core import ucfl
    from repro.models import lenet

    n, vb = 204, 50
    chunk = ucfl.init_chunk(M_FEMNIST, n, vb)
    assert chunk == 50
    params0 = lenet.init(jax.random.PRNGKey(0), input_hw=(28, 28),
                         channels=1, num_classes=62)
    compiled = ucfl._special_round_uploads.lower(
        jax.tree.map(lambda a: spec(a.shape, a.dtype), params0),
        spec((M_FEMNIST, n, 28, 28, 1)), spec((M_FEMNIST, n), I32),
        apply_fn=lenet.apply, var_batch_size=vb, chunk_size=chunk,
        mesh=None).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 12e9, total


@pytest.mark.parametrize("k,m", [(2, 20), (2, 1024)])
def test_mix_aggregate_kernel_compiles(spec, k, m):
    compiled = _compile(lambda w, t: ops.mix_aggregate(w, t, impl="pallas"),
                        spec((k, m)), spec((m, D)))
    assert "tpu_custom_call" in compiled.as_text()


def test_kmeans_assign_kernel_compiles(spec):
    compiled = _compile(lambda p, c: ops.kmeans_assign(p, c, impl="pallas"),
                        spec((20, 20)), spec((2, 20)))
    assert "tpu_custom_call" in compiled.as_text()


def test_rehearsal_shapes_match_the_paper_model():
    """D is LeNet's flat width at paper scale, aligned to 128 lanes."""
    from repro.models import lenet

    params = lenet.init(jax.random.PRNGKey(0), input_hw=(28, 28),
                        channels=1, num_classes=20)
    d = sum(x.size for x in jax.tree.leaves(params))
    assert ops.aligned_dim(d) == D
