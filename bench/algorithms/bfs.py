"""Graph500 BFS (kernel 2): the reference the answers are compared with.

The reference is SciPy's breadth-first order (``scipy.sparse.csgraph``),
independent of the program, with levels read off the BFS tree by pointer
jumping.  An answer is the full level vector of one search key: a
float32 hop count per vertex, ``inf`` where the key does not reach.

``levels(..., max_edges_per_vertex=k)`` is the control: the reference
with each vertex expanding only its first ``k`` edges, which breaks the
configuration's guarantee that every edge of a reached vertex is
traversed.  ``levels(..., dtype=bfloat16)`` is the reference computed
in the precision below float32; it is exact for levels up to 256, so it
is no control for a graph this shallow (PERF.md).
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import breadth_first_order

APP = "bfs"                  # the program's app name (graph/apps.py)


def _matrix(row_ptr, col_idx, n):
    data = np.ones(col_idx.shape[0], np.int8)
    return sp.csr_matrix((data, col_idx, row_ptr), shape=(n, n))


def truncated(row_ptr, col_idx, k):
    """The CSR with each row cut to its first ``k`` entries."""
    deg = np.minimum(np.diff(row_ptr), k)
    new_ptr = np.zeros_like(row_ptr)
    np.cumsum(deg, out=new_ptr[1:])
    starts = np.repeat(row_ptr[:-1] - new_ptr[:-1], deg)
    keep = starts + np.arange(new_ptr[-1])
    return new_ptr, col_idx[keep]


def levels(graph: dict, root: int, *, max_edges_per_vertex=None,
           dtype=np.float32) -> np.ndarray:
    """Hop level of every vertex from ``root`` (``inf`` if unreached)."""
    n = graph["n"]
    row_ptr, col_idx = graph["row_ptr"], graph["col_idx"]
    if max_edges_per_vertex is not None:
        row_ptr, col_idx = truncated(row_ptr, col_idx, max_edges_per_vertex)
    order, pred = breadth_first_order(_matrix(row_ptr, col_idx, n), root,
                                      directed=True,
                                      return_predecessors=True)
    # depth in the BFS tree by pointer jumping: each round adds the
    # depth of the current ancestor and jumps to the ancestor's ancestor
    anc = pred.astype(np.int64)
    has = anc >= 0
    depth = has.astype(dtype)
    while has.any():
        a = anc[has]
        depth[has] = depth[has] + depth[a]
        anc[has] = anc[a]
        has = anc >= 0
    out = np.full(n, np.inf, np.float32)
    out[order] = depth[order].astype(np.float32)
    return out


def traversed_edges(graph: dict, answer: np.ndarray) -> int:
    """Graph500's count for TEPS: input edges (each undirected edge once,
    self-loops and duplicates as generated) with both endpoints reached."""
    reached = np.isfinite(answer)
    return int(np.sum(reached[graph["src"]] & reached[graph["dst"]]))


def mismatches(answer: np.ndarray, reference: np.ndarray) -> int:
    """Vertices whose level differs from the reference (``inf`` equals
    ``inf``; a NaN always differs)."""
    return int(np.sum(~(answer == reference)))


def query(eng, root: int):
    """The program's initial state for one search key."""
    return eng.init_state(seed_idx=root, seed_val=0.0)
