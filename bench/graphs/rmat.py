"""Graph500 Kronecker (RMAT) generator: the benchmark's own copy.

The quadrant draw, the vertex permutation and the random stream follow
the program's ``graph/rmat.py:rmat_edges``; the number of input edges
is Graph500's, ``M = edge_factor * 2**scale`` undirected edge tuples,
where ``rmat_edges`` makes half as many.  The seed is the
configuration's ``graph_seed``: every run of a configuration serves the
same graph, and the run's seed draws the search keys (PERF.md says why).
It is kept here so that a change to the program cannot change the
benchmark's input.

``generate`` returns the undirected input edge list (``M`` pairs,
self-loops and duplicates kept, as Graph500 generates them) and the CSR
of both directions (``2 * M`` entries), sorted stably by source.
"""
from __future__ import annotations

import numpy as np


def edge_list(scale: int, edge_factor: int, a: float, b: float, c: float,
              seed: int):
    """The ``(src, dst)`` input edge list of one RMAT graph: Graph500's
    ``M = edge_factor * 2**scale`` edge tuples."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    m = n * edge_factor
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    ab, abc = a + b, a + b + c
    for bit in range(scale):
        q = rng.random(m)
        src_bit = q >= ab
        cond = np.where(src_bit, c / max(c + (1.0 - abc), 1e-12), a / ab)
        dst_bit = rng.random(m) >= cond
        src |= src_bit.astype(np.int64) << bit
        dst |= dst_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    return perm[src], perm[dst]


def csr_both_directions(src: np.ndarray, dst: np.ndarray, n: int):
    """``(row_ptr, col_idx)`` of the graph with every edge both ways."""
    s2 = np.concatenate([src, dst])
    d2 = np.concatenate([dst, src])
    order = np.argsort(s2, kind="stable")
    row_ptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(s2, minlength=n), out=row_ptr[1:])
    return row_ptr, d2[order].astype(np.int32)


def generate(cfg: dict) -> dict:
    """The configuration's graph, from its ``graph_seed``."""
    if cfg["weighted"]:
        raise ValueError("the RMAT generator here makes unweighted graphs")
    n = 1 << cfg["scale"]
    src, dst = edge_list(cfg["scale"], cfg["edge_factor"], cfg["rmat_a"],
                         cfg["rmat_b"], cfg["rmat_c"], cfg["graph_seed"])
    row_ptr, col_idx = csr_both_directions(src, dst, n)
    return dict(n=n, src=src, dst=dst, row_ptr=row_ptr, col_idx=col_idx)
