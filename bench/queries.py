"""The traffic generator: search keys from a traffic mix's parameters.

A mix file (``traffic/<name>.json``) gives ``search_keys`` (how many
keys a run may send), ``min_degree`` (Graph500: 1, not counting
self-loops) and ``in_flight`` (queries in flight at once; the closed
loop here sends the next key when the previous answer is back, so it
must be 1).  Keys are drawn uniformly without replacement from the
vertices that qualify, from a stream of their own seeded by the run's
seed, so one seed gives one graph and one key order.
"""
from __future__ import annotations

import numpy as np

KEY_STREAM = 0x6b657973          # separates the key stream from the graph's


def degrees_without_self_loops(src: np.ndarray, dst: np.ndarray,
                               n: int) -> np.ndarray:
    keep = src != dst
    return (np.bincount(src[keep], minlength=n)
            + np.bincount(dst[keep], minlength=n))


def search_keys(graph: dict, mix: dict, seed: int) -> np.ndarray:
    if mix["in_flight"] != 1:
        raise ValueError("the closed loop keeps one query in flight")
    deg = degrees_without_self_loops(graph["src"], graph["dst"], graph["n"])
    candidates = np.flatnonzero(deg >= mix["min_degree"])
    rng = np.random.default_rng([KEY_STREAM, seed])
    return rng.choice(candidates, size=min(mix["search_keys"],
                                           candidates.shape[0]),
                      replace=False)
