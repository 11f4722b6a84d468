"""Graph500 kernel 3's weighted RMAT graph: ``rmat.py``'s graph with a
weight on every edge.

The edge list and the CSR are ``rmat.py``'s for the same ``graph_seed``.
Each undirected input edge then draws one float32 weight, uniform in
[0, 1) (Graph500 specification 3.0, kernel 3), from a stream of its own
(the configuration's ``weight_seed``), so the graph's shape does not
depend on the weights.  Both directions of an edge carry its weight, in
the CSR's order.
"""
from __future__ import annotations

import os

import numpy as np

from loader import load_module

rmat = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "rmat.py"), "graph_rmat")


def edge_weights(m: int, seed: int) -> np.ndarray:
    """One float32 weight in [0, 1) per input edge, in input order."""
    return np.random.default_rng(seed).random(m, dtype=np.float32)


def csr_both_directions(src: np.ndarray, dst: np.ndarray, w: np.ndarray,
                        n: int):
    """``rmat.csr_both_directions`` with each edge's weight carried to
    both of its entries: ``(row_ptr, col_idx, weights)``."""
    row_ptr, col_idx = rmat.csr_both_directions(src, dst, n)
    order = np.argsort(np.concatenate([src, dst]), kind="stable")
    return row_ptr, col_idx, np.concatenate([w, w])[order]


def generate(cfg: dict) -> dict:
    """The configuration's weighted graph, from its ``graph_seed`` and
    ``weight_seed``."""
    if not cfg["weighted"]:
        raise ValueError("this generator makes weighted graphs")
    n = 1 << cfg["scale"]
    src, dst = rmat.edge_list(cfg["scale"], cfg["edge_factor"],
                              cfg["rmat_a"], cfg["rmat_b"], cfg["rmat_c"],
                              cfg["graph_seed"])
    w = edge_weights(src.shape[0], cfg["weight_seed"])
    row_ptr, col_idx, weights = csr_both_directions(src, dst, w, n)
    return dict(n=n, src=src, dst=dst, row_ptr=row_ptr, col_idx=col_idx,
                weights=weights)
