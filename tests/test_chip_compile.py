"""Compile the main path's kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with JAX, compiles each
kernel at the engine's real shapes for a chip that is described and not
attached. It refuses what interpret mode cannot see — block shapes not
aligned to the (8, 128) tiling, scalar memory (SMEM, 1 MiB on v5e) or
VMEM beyond the chip's limits.

Shapes are those of the GIS engine at the paper's ``scale=1.0``
(785,891 vertices, 8 neighbor slots, 128-op chunks), read off
``BatchedTrafficEngine.build_sssp_problem`` and ``ensure_full_layout``:
a windowed chunk pads to 524,288 rows, the whole-graph redo layout to
786,432. The sharded whole-graph solve, heuristic rows included, is
compiled there too.

The topology is described inside a module fixture: only the process that
runs these tests loads the TPU library, and every worker collects the
same tests.
"""

from __future__ import annotations

import os

import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

C = 128          # ops per chunk: the frontier's column count
SLOTS = 8        # GIS in-neighbor cap at scale=1.0
GIS_ROWS = {"windowed": 524_288, "whole_graph": 786_432}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described chip's executables cannot be read back from the
    # persistent cache; keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("layout", sorted(GIS_ROWS))
@pytest.mark.parametrize("mode", ["sum", "min"])
def test_frontier_kernel_compiles_at_gis_paper_scale(one_chip, layout, mode):
    from repro.kernels.frontier.kernel import _frontier_gather_jit

    rows = GIS_ROWS[layout]
    compiled = _frontier_gather_jit.lower(
        _shape(one_chip, (rows, C), jnp.float32),
        _shape(one_chip, (rows, SLOTS), jnp.int32),
        _shape(one_chip, (rows, SLOTS), jnp.float32),
        mode=mode, c_tile=128, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # Frontier in, relaxed frontier out, and the layout: all in 16 GB HBM.
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < 16e9


def test_bsr_spmm_compiles_within_smem(one_chip):
    """``bsr_spmm`` prefetches its whole ``[block_rows, slots]`` column and
    mask tables into SMEM, padded to 128 lanes: 256 block-rows (32,768
    vertices) fit; paper-scale graphs (6,000+ block-rows) do not."""
    from repro.kernels.bsr_spmm.kernel import bell_matmul

    nbr, nnz, bs = 256, 16, 128
    compiled = bell_matmul.lower(
        _shape(one_chip, (nbr, nnz, bs, bs), jnp.float32),
        _shape(one_chip, (nbr, nnz), jnp.int32),
        _shape(one_chip, (nbr, nnz), jnp.int32),
        _shape(one_chip, (nbr * bs, 4), jnp.float32),
        interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_whole_graph_solve_compiles_at_gis_paper_scale(topo):
    """``jit_solve_full_body`` on a one-chip mesh at 786,432 rows × 128 ops:
    the heuristic rows are computed inside it (the ±1 ulp rounding step
    on the ``sqrt``'s bit pattern and the integer midpoint check) from
    ``[W]`` and ``[C]`` coordinates, and the whole program fits in HBM."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.traffic_sharded import ShardedTrafficReplayer
    from repro.graphs import datasets

    rows = GIS_ROWS["whole_graph"]
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    rep = ShardedTrafficReplayer(datasets.load("gis", scale=0.002), "gis_short", mesh)
    op, rep_ = NamedSharding(mesh, P("data", None)), NamedSharding(mesh, P())
    i32, f32 = jnp.int32, jnp.float32
    per_op = [_shape(op, (1, C), t) for t in (i32, i32, i32, jnp.bool_, f32, f32)]
    layout = [_shape(rep_, (rows,), i32)] * 3 + [
        _shape(rep_, (rows, SLOTS), i32), _shape(rep_, (rows, SLOTS), f32),
        _shape(rep_, (1024,), i32), _shape(rep_, (1024,), i32), _shape(rep_, (1024,), f32),
        _shape(rep_, (rows,), f32), _shape(rep_, (rows,), f32), _shape(rep_, (), f32),
    ]
    compiled = rep._solve_full_fn.lower(*per_op, *layout).compile()
    text = compiled.as_text()
    assert "sqrt" in text and "bitcast-convert" in text
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    # No [W, C] heuristic input: the per-op arguments are [1, C] columns.
    assert mem.argument_size_in_bytes < rows * C
    assert used < 16e9
