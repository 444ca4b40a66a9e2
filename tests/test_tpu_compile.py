"""Compile the device programs of Palpatine's main path for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology, and refuses what the chip would
refuse (block shapes off the (8, 128) tiling, kernels past the scoped
VMEM limit) — faults that interpret-mode tests cannot show.  Nothing
runs.  Shapes are the paper-scale SEQB ones ``chip_smoke.py`` drives.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.bitmap_support import ops as bops
from repro.kernels.decision_walk import ops as dw_ops
from repro.kernels.decision_walk.decision_walk import decision_walk_step


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described chip's programs can be written to the persistent cache
    but never read back; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(sharding, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("p,k,s,w", [
    (64, 512, 5000, 1),      # a SEQB level: 5,000 warm sessions, 1 word
    (300, 600, 5000, 1),     # K past a 128-lane block, not a multiple
    (8, 1024, 5000, 4),      # 4-word sessions (up to 128 accesses)
])
def test_frontier_join_compiles(one_chip, no_compile_cache, p, k, s, w):
    # every padded program the join of these shapes calls
    for pb, kb, sb, wb in bops.frontier_calls(p, k, s, w):
        compiled = bops.frontier_program.lower(
            _shape(one_chip, (wb, pb, sb)), _shape(one_chip, (wb, kb, sb)),
            interpret=False).compile()
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,s,w", [(512, 5000, 1), (600, 5000, 4)])
def test_sstep_join_compiles(one_chip, no_compile_cache, k, s, w):
    compiled = bops.sstep_join_support.lower(
        _shape(one_chip, (s, w)), _shape(one_chip, (k, s, w)),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decision_walk_compiles(one_chip, no_compile_cache):
    # every array of a 100,000-node forest pads to its 131,072 rung
    n, c = dw_ops.node_bucket(100_000), 256      # nodes, contexts
    assert n == 131_072
    i32 = jnp.int32
    node = _shape(one_chip, (n,), i32)
    compiled = decision_walk_step.lower(
        *([node] * 10), _shape(one_chip, (3 * c + 2,), i32),
        p_depth=2, search_steps=n.bit_length()).compile()
    # one packed output: five state columns and the wave mask's
    # N / 32 words per context, all int32
    cols = 5 + n // 32
    out = compiled.out_info
    assert (out.shape, out.dtype) == ((c, cols), jnp.int32)
    # on the chip C (256, whole lanes) is the minor dimension and the
    # 4,101 columns fill whole 8-row tiles: 4,104 of them
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes == c * (-(-cols // 8) * 8) * 4
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < 16e9
