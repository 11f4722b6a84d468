"""Hand-worked cases of the benchmark's counts, and its reference
against the program's oracle."""
import dataclasses

import numpy as np
import pytest

import bench_testlib as tl
from loader import Benchmark
import queries


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


@pytest.fixture(scope="module")
def bfs(bench):
    return bench.algorithm("bfs")


def _small(src, dst, n, bench):
    rmat = bench.graph_generator("rmat")
    src, dst = np.asarray(src), np.asarray(dst)
    row_ptr, col_idx = rmat.csr_both_directions(src, dst, n)
    return dict(n=n, src=src, dst=dst, row_ptr=row_ptr, col_idx=col_idx)


def test_traversed_edges_hand_worked(bench, bfs):
    # 0-1, 1-2, 2-2 (self-loop), 1-0 (duplicate), 3-4: from root 0 the
    # component {0, 1, 2} holds four input edges; 3-4 is not reached
    g = _small([0, 1, 2, 1, 3], [1, 2, 2, 0, 4], 5, bench)
    levels = bfs.levels(g, 0)
    assert levels.tolist() == [0, 1, 2, np.inf, np.inf]
    assert bfs.traversed_edges(g, levels) == 4
    assert bfs.traversed_edges(g, bfs.levels(g, 3)) == 1


def test_query_s_is_the_mean_wall_time(bench):
    import harness
    read = bench.reader("query_s").read
    qs = [harness.Query(root=0, t=(1.0, 1.5, 3.0, t3), supersteps=1)
          for t3 in (4.0, 3.0, 5.0)]
    run = harness.Run(cell={}, config={}, setup_s=0.0, window_s=6.0,
                      queries=qs, peak_bytes=None, trace=None)
    assert read(run) == 3.0                           # (3 + 2 + 4) / 3
    assert read(dataclasses.replace(run, queries=qs[:1])) == 3.0


def test_rmat_makes_graph500_edge_count(bench):
    rmat = bench.graph_generator("rmat")
    cfg = tl.tiny_config(bench, tl.CELL_1, scale=10)
    g = rmat.generate(cfg)
    assert g["src"].shape == (16 << 10,)              # M = 16 * 2^10
    assert g["col_idx"].shape == (2 * (16 << 10),)    # both directions
    assert g["row_ptr"][-1] == 2 * (16 << 10)


def test_mismatches_count_inf_and_nan(bfs):
    ref = np.array([0, 1, np.inf], np.float32)
    assert bfs.mismatches(ref.copy(), ref) == 0
    assert bfs.mismatches(np.array([0, 2, np.inf], np.float32), ref) == 1
    assert bfs.mismatches(np.array([0, np.nan, 1], np.float32), ref) == 2


def test_reference_agrees_with_program_oracle_at_scale_10(bench, bfs):
    from repro.graph.csr import CSR
    from repro.graph.oracles import bfs_oracle
    cfg = tl.tiny_config(bench, tl.CELL_1, scale=10)
    g = bench.graph_generator("rmat").generate(cfg)
    csr = CSR(row_ptr=g["row_ptr"], col_idx=g["col_idx"], weights=None,
              n_cols=g["n"])
    for root in queries.search_keys(g, bench.traffic("g500roots"), 11)[:8]:
        np.testing.assert_array_equal(bfs.levels(g, int(root)),
                                      bfs_oracle(csr, int(root)))


def test_search_keys_follow_the_seed(bench):
    cfg = tl.tiny_config(bench, tl.CELL_1, scale=10)
    mix = bench.traffic("g500roots")
    g = bench.graph_generator("rmat").generate(cfg)
    a = queries.search_keys(g, mix, 2**31 + 7)
    b = queries.search_keys(g, mix, 2**31 + 7)
    c = queries.search_keys(g, mix, 2**31 + 8)
    assert a.shape == (64,) and len(set(a.tolist())) == 64
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    deg = queries.degrees_without_self_loops(g["src"], g["dst"], g["n"])
    assert (deg[a] >= 1).all()
