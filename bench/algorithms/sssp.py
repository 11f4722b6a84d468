"""Graph500 SSSP (kernel 3): the reference the answers are compared with.

An answer is the full distance vector of one search key: the float32
shortest distance of every vertex, ``inf`` where the key does not reach.
The reference is plain NumPy and independent of the program: a
frontier relaxation (Bellman-Ford by rounds).  Each round relaxes every
edge of the vertices whose distance fell in the round before, rounds
``d[u] + w`` to the working precision, keeps the least candidate of
each vertex, and lowers ``d`` where that candidate is smaller; it stops
when no distance falls.

Why that is exact, and the same answer as a float32 Dijkstra: weights
are non-negative, so for a distance d the rounded sum fl(d + w) is at
least d, and fl(x + w) <= fl(y + w) where x <= y (rounding to nearest
is monotone).  The distance the relaxation settles on is then, for
every vertex, the least over all paths from the key of the path's
length summed edge by edge in rounded arithmetic: an edge that could
still lower it would have been relaxed, and along a least path each
vertex's distance is at most that path's partial sum, by induction
over the path.  That least value is unique, so every label-correcting
order that runs until nothing improves (the program's, Dijkstra's, the
rounds here) reaches it bit for bit.

``levels(..., max_edges_per_vertex=k)`` is the control: the reference
with each vertex relaxing only its first ``k`` edges, which breaks the
configuration's guarantee that every improvement is re-expanded over all
of the vertex's edges.  ``levels(..., dtype=bfloat16)`` is the reference
in the precision below float32: weights and distances rounded to it.
"""
from __future__ import annotations

import numpy as np

APP = "sssp"                 # the program's app name (graph/apps.py)


def _kept_edges(row_ptr, k):
    """The row pointer of the CSR with each row cut to its first ``k``
    entries, and the positions of the entries kept."""
    deg = np.minimum(np.diff(row_ptr), k)
    new_ptr = np.zeros_like(row_ptr)
    np.cumsum(deg, out=new_ptr[1:])
    starts = np.repeat(row_ptr[:-1] - new_ptr[:-1], deg)
    return new_ptr, starts + np.arange(new_ptr[-1])


def _edges_of(row_ptr, frontier):
    """The positions of every edge of the ``frontier`` vertices, and the
    vertex each starts from."""
    lo = row_ptr[frontier]
    deg = row_ptr[frontier + 1] - lo
    first = np.cumsum(deg) - deg
    pos = np.repeat(lo - first, deg) + np.arange(int(deg.sum()))
    return pos, np.repeat(frontier, deg)


def levels(graph: dict, root: int, *, max_edges_per_vertex=None,
           dtype=np.float32) -> np.ndarray:
    """Shortest distance of every vertex from ``root`` (``inf`` if
    unreached), computed in ``dtype`` and returned as float32."""
    n = graph["n"]
    row_ptr, col_idx = graph["row_ptr"], graph["col_idx"]
    weights = graph["weights"]
    if max_edges_per_vertex is not None:
        row_ptr, keep = _kept_edges(row_ptr, max_edges_per_vertex)
        col_idx, weights = col_idx[keep], weights[keep]

    def rounded(x):          # float32 arithmetic, rounded to ``dtype``
        return np.asarray(x, np.float32).astype(dtype).astype(np.float32)

    weights = rounded(weights)
    dist = np.full(n, np.inf, np.float32)
    dist[root] = 0.0
    frontier = np.array([root], np.int64)
    while frontier.size:
        pos, u = _edges_of(row_ptr, frontier)
        v = col_idx[pos]
        cand = rounded(dist[u] + weights[pos])
        best = np.full(n, np.inf, np.float32)
        np.minimum.at(best, v, cand)
        frontier = np.flatnonzero(best < dist)
        dist[frontier] = best[frontier]
    return dist


def traversed_edges(graph: dict, answer: np.ndarray) -> int:
    """Graph500's count for TEPS: input edges (each undirected edge once,
    self-loops and duplicates as generated) with both endpoints reached."""
    reached = np.isfinite(answer)
    return int(np.sum(reached[graph["src"]] & reached[graph["dst"]]))


def mismatches(answer: np.ndarray, reference: np.ndarray) -> int:
    """Vertices whose distance differs from the reference, compared
    exactly (``inf`` equals ``inf``; a NaN always differs)."""
    return int(np.sum(~(answer == reference)))


def query(eng, root: int):
    """The program's initial state for one search key."""
    return eng.init_state(seed_idx=root, seed_val=0.0)
