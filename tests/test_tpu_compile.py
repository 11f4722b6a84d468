"""The engine's main path compiles for a TPU v5e — with no chip attached.

The TPU compiler is installed with JAX; it compiles for a *described*
``v5e:2x2`` topology.  These tests catch what interpret mode cannot: a
Pallas block that violates the chip's (8, 128) tiling, a kernel that
asks for more fast memory than it may use, a program that does not fit.

  * the four engine kernels compile (as Mosaic ``tpu_custom_call``s) at
    the chip smoke test's shapes: a 4096-tile x 64-record stream into a
    2**22-entry mailbox;
  * the dense BFS chunk step (the scanned superstep loop) compiles for
    4096 tiles and 2**22 vertices;
  * the OQ emit's lane -> slot map compiles to a dense compare with no
    gather, no loop and no scratch buffer;
  * the chunk step's lowered program does not grow with the edge count:
    the graph is an argument of the program, never a baked-in constant.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import DataLocalEngine, EngineConfig
from repro.core.tilegrid import square_grid
from repro.graph import apps, rmat_edges
from repro.kernels import deliver_fused, histogram_bin, relax_min, \
    segment_combine

TILES = 4096
RECORDS = TILES * 64          # 4096 tiles x oq_cap 64
MAILBOX = 1 << 22             # RMAT-22 vertices

F32, I32 = jnp.float32, jnp.int32

# name -> (kernel with compiled Mosaic lowering, argument shapes/dtypes)
KERNELS = {
    "relax_min": (functools.partial(relax_min.relax, combine="min",
                                    interpret=False),
                  [((MAILBOX,), F32), ((MAILBOX,), F32),
                   ((MAILBOX,), jnp.bool_)]),
    "deliver_fused": (functools.partial(deliver_fused.deliver_fused,
                                        combine="min", interpret=False),
                      [((RECORDS,), I32), ((RECORDS,), F32),
                       ((MAILBOX,), F32)]),
    "segment_combine": (functools.partial(segment_combine.segment_combine,
                                          num_segments=RECORDS,
                                          combine="min", presorted=True,
                                          interpret=False),
                        [((RECORDS,), I32), ((RECORDS,), F32)]),
    "histogram_bin": (functools.partial(histogram_bin.histogram_bin,
                                        num_bins=MAILBOX, interpret=False),
                      [((RECORDS,), I32)]),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _chunk_lowering(eng, state, sharding, graph_shapes=None):
    """Lower ``eng``'s chunk program (its scanned superstep loop) for
    the described chip, from shapes alone."""
    sds = lambda a: _on(sharding, a.shape, a.dtype)        # noqa: E731
    graph = graph_shapes or {k: sds(v) for k, v in eng.graph.items()}
    return eng._chunk.lower(
        graph, {k: sds(v) for k, v in state.items()},
        _on(sharding, (), jnp.bool_), _on(sharding, (), jnp.bool_),
        _on(sharding, (), I32), length=eng.cfg.run_chunk)


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    compiled = jax.jit(fn).lower(
        *[_on(one_chip, s, d) for s, d in shapes]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bfs_chunk_step_compiles_for_v5e(one_chip):
    """Dense, unproxied BFS chunk step at 4096 tiles and 2**22 vertices
    with the Graph500 edge count (2**26) — the smoke test's phase (a)."""
    n = MAILBOX
    zeros = np.zeros(n, np.int32)
    cfg = EngineConfig(grid=square_grid(TILES), n_src=n, n_dst=n)
    eng = DataLocalEngine(apps.BFS_SPEC, cfg, zeros, zeros,
                          np.zeros(1, np.int32))
    state = eng.init_state(seed_idx=0, seed_val=0.0)
    graph = {k: _on(one_chip, v.shape, v.dtype)
             for k, v in eng.graph.items()}
    graph["col_idx"] = _on(one_chip, (16 * n,), I32)
    compiled = _chunk_lowering(eng, state, one_chip, graph).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2**30      # far inside 16 GB of HBM


def test_chunk_program_size_independent_of_edges(one_chip):
    """Two graphs with the same vertices and 2x the edges lower to
    programs of identical size: no graph array is baked into the
    program as a constant (which would also recompile per graph)."""
    grid = square_grid(64)
    sizes = []
    for edge_factor in (16, 32):
        g = rmat_edges(10, edge_factor=edge_factor, seed=3)
        eng, state, _ = apps.engine_and_state("bfs", g, grid, root=0)
        sizes.append(len(_chunk_lowering(eng, state, one_chip).as_text()))
    assert sizes[0] == sizes[1], sizes


def test_emit_lanes_compile_dense(one_chip):
    """The OQ emit's lane -> slot map at the cells' shapes (4096 tiles,
    64 slots, oq_cap 64) is one dense compare fused with its reductions:
    no gather, no search loop, and no (tiles, lanes, slots) buffer."""
    from repro.core.engine import _emit_lanes
    ints = _on(one_chip, (TILES, 64), I32)
    compiled = jax.jit(_emit_lanes, static_argnums=4).lower(
        ints, ints, ints, _on(one_chip, (TILES, 64), F32), 64).compile()
    hlo = compiled.as_text()
    assert " gather(" not in hlo and " while(" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes == 0
