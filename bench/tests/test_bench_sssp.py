"""The SSSP cell (Graph500 kernel 3): its weighted graph, its reference,
and the comparison that decides ``correct``, at tiny sizes on the CPU.

The reference is held to an independent float32 Dijkstra bit for bit,
and to SciPy's float64 Dijkstra within float32's rounding; the control
and the precision below float32 both come out not correct, and so does
the program with its re-expansion of improved vertices taken away."""
import dataclasses
import heapq
import io
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

import bench_testlib as tl
import control
import harness
import queries
from loader import Benchmark
from repro.core.engine import DataLocalEngine
from repro.graph import apps

CELL = "sssp.rmat18.1pkg.g500roots"
CONFIG = "g500-sssp-rmat18-1pkg"


def run_tiny(seed: int = 5, seconds: float = 0.3) -> dict:
    """``bench_testlib.run_tiny`` for the SSSP cell."""
    return tl.run_tiny(CELL, seed, seconds)


@pytest.fixture(scope="module")
def bench():
    return Benchmark()


@pytest.fixture(scope="module")
def cfg(bench):
    return tl.tiny_config(bench, CELL, scale=10)


@pytest.fixture(scope="module")
def graph(bench, cfg):
    return bench.graph_generator(cfg["graph"]).generate(cfg)


@pytest.fixture(scope="module")
def sssp(bench):
    return bench.algorithm("sssp")


@pytest.fixture(scope="module")
def keys(bench, graph):
    return [int(k) for k in queries.search_keys(
        graph, bench.traffic("g500roots"), 7)[:3]]


def heap_dijkstra(graph, root):
    """Textbook Dijkstra in float32, with each vertex's hop count on the
    path it settles by: ``(dist, hops)``."""
    row_ptr, col, w = graph["row_ptr"], graph["col_idx"], graph["weights"]
    dist = np.full(graph["n"], np.inf, np.float32)
    hops = np.zeros(graph["n"], np.int64)
    dist[root] = 0.0
    heap = [(np.float32(0.0), root)]
    while heap:
        d, u = heapq.heappop(heap)
        if d > dist[u]:
            continue
        for e in range(row_ptr[u], row_ptr[u + 1]):
            nd = np.float32(d + w[e])
            if nd < dist[col[e]]:
                dist[col[e]] = nd
                hops[col[e]] = hops[u] + 1
                heapq.heappush(heap, (nd, int(col[e])))
    return dist, hops


def test_cell_is_cell_1_with_weights_and_sssp(bench):
    """The benchmark's SSSP cell runs cell 1's traffic, chips and machine;
    its configuration differs from cell 1's only by the weights and the
    algorithm."""
    cell, bfs = bench.cell(CELL), bench.cell(tl.CELL_1)
    assert cell["config"] == CONFIG
    assert (cell["traffic"], cell["chips"]) == (bfs["traffic"], bfs["chips"])
    cfg, ref = bench.config(CONFIG), bench.config(bfs["config"])
    differ = {k for k in set(cfg) | set(ref) if cfg.get(k) != ref.get(k)}
    assert differ == {"name", "source", "deployment", "graph", "weighted",
                      "weight_seed", "algorithm", "guarantees", "assumed"}
    assert (cfg["graph"], cfg["weighted"], cfg["algorithm"]) == (
        "rmat_weighted", True, "sssp")


def test_tiny_cell_is_correct():
    res = run_tiny()
    assert res["correct"] and res["attempted"] >= 1
    assert res["checks"]["level_mismatches"]["value"] == 0


def test_reference_equals_float32_dijkstra(graph, sssp, keys):
    for root in keys:
        ref = sssp.levels(graph, root)
        want, _ = heap_dijkstra(graph, root)
        assert ref.dtype == np.float32
        assert np.isfinite(ref).sum() > 1
        assert ref.tobytes() == want.tobytes()


def test_reference_within_float32_rounding_of_float64(graph, sssp, keys):
    """A float32 path length summed over h edges of float32 weights is
    within gamma_h = h*u/(1 - h*u) of the exact sum, u = 2**-24 (one
    rounding to nearest per addition, the weights exact).  The float32
    shortest distance d32 is the least such sum; the exact one d, over a
    path of h* edges, is within float64's rounding of SciPy's d64.  So
    d32 <= (1 + gamma_h*) d and d32 >= (1 - gamma_h') d, h' the edges of
    d32's own path, and with h = max(h*, h'): |d32 - d64| <= (h + 1) u
    d64, the one extra u covering gamma_h's second order term (h*u is
    below 2**-12 here) and float64's own rounding."""
    n = graph["n"]
    # SciPy sums duplicate entries: keep each edge's least weight
    order = np.lexsort((graph["weights"], graph["col_idx"],
                        np.repeat(np.arange(n), np.diff(graph["row_ptr"]))))
    src = np.repeat(np.arange(n), np.diff(graph["row_ptr"]))[order]
    dst, w = graph["col_idx"][order], graph["weights"][order]
    first = np.ones(src.size, bool)
    first[1:] = (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])
    assert np.all(w > 0)            # no entry of the sparse graph is a 0
    mat = sp.csr_matrix((w[first].astype(np.float64),
                         (src[first], dst[first])), shape=(n, n))
    u = 2.0 ** -24
    for root in keys:
        d64, pred = dijkstra(mat, directed=True, indices=root,
                             return_predecessors=True)
        hops64 = np.zeros(n, np.int64)
        for v in np.flatnonzero(np.isfinite(d64)):
            p = v
            while pred[p] >= 0:
                hops64[v] += 1
                p = pred[p]
        d32 = sssp.levels(graph, root)
        _, hops32 = heap_dijkstra(graph, root)
        reached = np.isfinite(d64)
        assert np.array_equal(np.isfinite(d32), reached)
        h = np.maximum(hops64, hops32)[reached]
        err = np.abs(d32[reached].astype(np.float64) - d64[reached])
        assert np.all(err <= (h + 1) * u * d64[reached])
        assert err.max() > 0        # float32 rounds: the bound is not idle


@pytest.mark.parametrize("variant", ["edge_cap", "bfloat16"])
def test_controls_are_not_correct(bench, cfg, variant):
    res = harness.run_cell(bench, CELL, 1, 0.5, False, jax.devices()[:1],
                           time.perf_counter(), config=cfg,
                           log=io.StringIO(),
                           build=control.reference_builder(
                               control.variants(cfg)[variant]))
    assert not res["correct"]
    assert res["checks"]["level_mismatches"]["value"] > 0


def _reactivation_off(monkeypatch):
    """The program's reactivation switched off for SSSP: an improved
    vertex never restarts its edge cursor."""
    monkeypatch.setattr(apps, "SSSP_SPEC", dataclasses.replace(
        apps.SSSP_SPEC, reactivate=False))


def _first_reach_only(monkeypatch):
    """A vertex expands on its first reach only: an improvement of a
    reached vertex lowers its value but restarts nothing, so the edges it
    streamed under the old value stand."""
    front = DataLocalEngine._front_dense

    def first_only(self, graph, state, tile_gids):
        out = front(self, graph, state, tile_gids)
        again = (out[0] < state["values"]) & jnp.isfinite(state["values"])
        # the same superstep with those records left unread: cursors and
        # emitted records as if the restarts had not happened
        kept = front(self, graph, dict(
            state, mail_flag=state["mail_flag"] & ~again), tile_gids)
        return out[:3] + kept[3:]

    monkeypatch.setattr(DataLocalEngine, "_front_dense", first_only)


FAULTS = {"reactivation_off": _reactivation_off,
          "first_reach_only": _first_reach_only}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = run_tiny()
    assert res["attempted"] >= 1
    assert not res["correct"]
    assert res["checks"]["level_mismatches"]["value"] > 0


def test_weighted_graph_is_rmat_with_symmetric_weights(bench, cfg, graph):
    plain = bench.graph_generator("rmat").generate(dict(cfg, weighted=False))
    for k in ("n", "src", "dst", "row_ptr", "col_idx"):
        assert np.array_equal(graph[k], plain[k]), k
    w = graph["weights"]
    assert w.dtype == np.float32 and w.shape == graph["col_idx"].shape
    assert w.min() >= 0.0 and w.max() < 1.0
    # each input edge's weight on both of its entries: the (u, v, w)
    # entries are the (v, u, w) entries
    u = np.repeat(np.arange(graph["n"]), np.diff(graph["row_ptr"]))
    v = graph["col_idx"]
    fwd = np.lexsort((w, v, u))
    bwd = np.lexsort((w, u, v))
    assert np.array_equal(u[fwd], v[bwd]) and np.array_equal(v[fwd], u[bwd])
    assert np.array_equal(w[fwd], w[bwd])
    # one draw per input edge, from the weight stream alone
    drawn = np.random.default_rng(cfg["weight_seed"]).random(
        graph["src"].size, dtype=np.float32)
    assert np.array_equal(np.sort(w), np.sort(np.concatenate([drawn, drawn])))
