"""The default classify path compiles for a TPU v5e at the paper's width.

Interpret mode cannot see what the chip's compiler refuses: unaligned
blocks, primitives with no Mosaic lowering (``cumsum``), reductions over
unsigned types, minor-dim reshapes.  These tests compile the jitted classify
step with ``mode="pallas"`` for a v5e that is described, not attached — the
installed TPU compiler runs on the CPU — at the paper-width
``PlaneProfile()`` and B=4096, for a single model and an 8-version zoo,
whose rows the kernel's wrapper groups by version into a buffer that
``memory_analysis()`` shows.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.packets import PacketBatch
from repro.core.plane import PlaneProfile, SwitchEngine, empty_program
from repro.kernels.classify_fused import block_rows

B = 4096
HBM_BYTES = 16 * 10**9   # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("versions", [1, 8])
def test_fused_classify_compiles_for_v5e(one_chip, no_compile_cache,
                                         versions):
    prof = PlaneProfile(max_versions=versions)
    on_chip = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    packed = jax.eval_shape(lambda: empty_program(prof))
    batch = jax.eval_shape(lambda: jax.tree.map(
        jnp.asarray, PacketBatch.make_request(
            np.zeros((B, prof.max_features), np.int32),
            max_features=prof.max_features, n_trees=prof.max_trees,
            n_hyperplanes=prof.max_hyperplanes)))
    engine = SwitchEngine(prof, mode="pallas")
    compiled = engine.lower(on_chip(packed), on_chip(batch)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes + mem.generated_code_size_in_bytes
            - mem.alias_size_in_bytes)
    assert 0 < need < HBM_BYTES
    # The rows are grouped by version into a buffer of
    # ceil(B / block_b) + V - 1 blocks (int16 features lane-padded to 128,
    # uint32 codes).  At V = 8 the compiler puts it in HBM, where
    # ``temp_size`` counts it; at V = 1 its small operands leave room to
    # keep it in VMEM, which ``temp_size`` does not count.
    bb = block_rows(prof.max_trees, prof.max_leaves,
                    prof.max_entries_per_layer, prof.levels)
    grouped = ((-(-B // bb) + versions - 1) * bb
               * (128 * 2 + prof.max_trees * 4))
    if versions == 8:
        assert grouped <= mem.temp_size_in_bytes < 2 * grouped
