"""The Pallas histogram compiles for a v5e — without the chip.

The TPU compiler is installed with jax; it compiles for a described,
unattached ``v5e:2x2`` topology.  Interpret-mode tests (test_kernels.py)
cannot see what Mosaic refuses — sub-tile reshapes, illegal block tilings —
so these compiles guard the kernel's layout at the shapes a fit issues:
F <= 8 and F > 8 per party (all padded columns, or a tree's compacted
feature subsample), node levels 1 and 256 (``frontier_cap``),
32 bins (128 for KDD Cup 99's categories), 2 (classification) and 3
(regression) stat channels.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.histogram import histogram_pallas


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip executable written to the persistent cache cannot be
    # read back without the chip; keep these compiles out of it (jax decides
    # once per process whether the cache is on, so reset that decision)
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("n,f,n_level,c", [
    (65_536, 8, 16, 2),       # F <= 8 (once refused: sub-tile shape cast)
    (65_536, 24, 1, 3),       # F > 8 (once refused: (512, 8) xb block), root
    (156_198, 84, 256, 2),    # target-marketing e-commerce party, cap level
    (156_198, 10, 256, 2),    # the same, compacted to a tree's 10 features
    (515_345, 23, 256, 3),    # Year Prediction quarter, regression channels
])
def test_histogram_compiles_for_v5e(one_chip, n, f, n_level, c):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda xb, seg, stats: histogram_pallas(xb, seg, stats, n_level, 32)
    ).lower(sds((n, f), jnp.uint8), sds((n,), jnp.int32),
            sds((n, c), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_level", [1, 128])
def test_histogram_compiles_for_v5e_at_128_bins(one_chip, n_level):
    """The KDD Cup 99 network operator's compacted 6 columns at 128 bins,
    one bin per category of its 70-category ``service`` column."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(
        lambda xb, seg, stats: histogram_pallas(xb, seg, stats, n_level, 128)
    ).lower(sds((494_021, 6), jnp.uint8), sds((494_021,), jnp.int32),
            sds((494_021, 2), jnp.float32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
