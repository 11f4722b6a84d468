"""The engine's churn counters: ``reactivations`` (cursor restarts of an
item already reached) and ``stale_edges`` (the edges it had streamed
under its old value).

Acceptance properties:
  * on a hand-built weighted graph at ``oq_cap=1``, where a vertex is
    improved after it has started streaming, both equal hand-counted
    values, on the dense and compacted fronts, the legacy and chunked
    loops, and the Pallas backend alike;
  * they are 0 on a path graph, and 0 for every app that never restarts
    a cursor;
  * they reach ``RunResult.counters`` and the ``engine.account`` span on
    one chip and on four, and the four-chip ``shard_map`` path counts
    them beside the one-chip distances.
"""
import glob
import os

import numpy as np
import pytest

from _subproc import run_devices

from repro.core.tilegrid import square_grid
from repro.graph import apps, oracles, rmat_edges
from repro.graph.csr import csr_from_edges

GRID = square_grid(16)

# One vertex a tile, so every tile streams one edge a superstep.  The
# root 0 reaches 1 over its heavy edge first (s0), and 1 streams 1->3,
# 1->4 (s1, s2) before 0->2->1 improves it (s3): a reactivation with 2
# stale edges.  3 (reached at s2, streamed 3->7) then improves at s4
# (1 stale edge), 4 and 7 at s5 (no edges streamed).
HAND_EDGES = [(0, 1, 10.0), (0, 2, 1.0), (1, 3, 1.0), (1, 4, 1.0),
              (1, 5, 1.0), (1, 6, 1.0), (2, 1, 1.0), (3, 7, 1.0)]
HAND_REACTIVATIONS = 4
HAND_STALE_EDGES = 3
HAND_DIST = [0.0, 2.0, 1.0, 3.0, 3.0, 3.0, 3.0, 4.0]


@pytest.fixture(scope="module")
def hand():
    src, dst, w = (np.array(c) for c in zip(*HAND_EDGES))
    return csr_from_edges(src, dst, 8, weights=w.astype(np.float32))


def _churn(res):
    c = res.run.counters
    return c.reactivations, c.stale_edges


@pytest.mark.parametrize("kw", [
    dict(), dict(run_chunk=0), dict(compaction=2),
    dict(compaction=2, run_chunk=0), dict(backend="pallas"),
    dict(chips=4)], ids=["dense", "legacy", "compact", "compact-legacy",
                         "pallas", "4chips"])
def test_hand_counted(hand, kw):
    res = apps.sssp(hand, 0, GRID, oq_cap=1, **kw)
    assert res.values.tolist() == HAND_DIST
    assert _churn(res) == (HAND_REACTIVATIONS, HAND_STALE_EDGES)


def test_dense_and_compacted_fronts_agree_under_churn():
    """RMAT SSSP with the proxy at ``oq_cap=1``: many restarts, counted
    the same on both fronts and on the Pallas backend."""
    g = rmat_edges(8, edge_factor=8, seed=1)
    root = int(np.argmax(g.out_degree()))
    px = apps.table2_proxy(GRID, "sssp")
    runs = [apps.sssp(g, root, GRID, proxy=px, oq_cap=1, **kw)
            for kw in (dict(), dict(compaction=3), dict(backend="pallas"))]
    assert _churn(runs[0])[0] > 0 and _churn(runs[0])[1] > 0
    for r in runs[1:]:
        assert _churn(r) == _churn(runs[0])
        assert np.array_equal(r.values, runs[0].values)
    assert np.array_equal(runs[0].values, oracles.sssp_oracle(g, root))


@pytest.mark.parametrize("name", ["sssp", "bfs"])
def test_path_graph_has_no_churn(name):
    n = 12
    a = np.arange(n - 1)
    g = csr_from_edges(np.concatenate([a, a + 1]),
                       np.concatenate([a + 1, a]), n,
                       weights=np.full(2 * (n - 1), 0.5, np.float32))
    res = getattr(apps, name)(g, 0, GRID, oq_cap=1)
    assert np.isfinite(res.values).all()
    assert _churn(res) == (0, 0)


def test_apps_without_restarts_read_zero():
    g = rmat_edges(6, edge_factor=4, seed=3)
    for res in (apps.pagerank(g, GRID, epochs=1, oq_cap=4),
                apps.spmv(g, np.ones(g.n_cols, np.float32), GRID, oq_cap=4)):
        assert _churn(res) == (0, 0)


def _account_args(log_dir):
    """Sum of the ``engine.account`` spans' churn arguments."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    tot = [0, 0]
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "engine.account":
                    st = dict(e.stats)
                    tot[0] += int(st["reactivations"])
                    tot[1] += int(st["stale_edges"])
    return tuple(tot)


@pytest.mark.parametrize("chips", [0, 4])
def test_account_span_and_chunk_spans_carry_churn(hand, chips, tmp_path):
    import jax

    from repro import obs
    rec = obs.TimelineRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        res = apps.sssp(hand, 0, GRID, oq_cap=1, chips=chips, run_chunk=4,
                        observer=rec)
    finally:
        jax.profiler.stop_trace()
    want = (HAND_REACTIVATIONS, HAND_STALE_EDGES)
    assert _churn(res) == want
    assert _account_args(str(tmp_path)) == want
    assert tuple(int(sum(s.stats[k].sum() for s in rec.spans))
                 for k in ("reactivations", "stale_edges")) == want


_SHARD_MAP_SNIPPET = """
import numpy as np
from repro.core.tilegrid import square_grid
from repro.graph import apps, rmat_edges
g = rmat_edges(8, edge_factor=8, seed=1)
grid = square_grid(16)
root = int(np.argmax(g.out_degree()))
px = apps.table2_proxy(grid, "sssp")
one = apps.sssp(g, root, grid, proxy=px, oq_cap=8)
four = apps.sssp(g, root, grid, proxy=px, oq_cap=8, chips=4,
                 backend="shard_map")
c = four.run.counters
print("EQUAL", np.array_equal(one.values, four.values))
print("CHURN", c.reactivations, c.stale_edges)
"""


def test_shard_map_counts_churn():
    """Four real XLA devices: the counters are there and non-negative,
    and the distances are one chip's."""
    out = dict(ln.split(" ", 1) for ln in run_devices(
        _SHARD_MAP_SNIPPET, n=4).splitlines() if " " in ln)
    assert out["EQUAL"] == "True"
    react, stale = (float(x) for x in out["CHURN"].split())
    assert react > 0 and stale >= 0
