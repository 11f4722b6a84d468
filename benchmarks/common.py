"""Shared benchmark utilities: dataset prep, timing, CSV rows."""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np                                    # noqa: E402

from repro.core.costmodel import D_CACHE_HIT          # noqa: E402,F401
from repro.core.netstats import MSG_BITS              # noqa: E402,F401

SCALE = int(os.environ.get("REPRO_BENCH_SCALE", "1"))


def dataset(scale_exp: int = 11, edge_factor: int = 8, seed: int = 1):
    """RMAT graph at benchmark scale (env REPRO_BENCH_SCALE bumps it)."""
    from repro.graph import rmat_edges
    return rmat_edges(scale_exp + (SCALE - 1), edge_factor=edge_factor,
                      seed=seed)


def wiki(scale: int = 12):
    from repro.graph import wikipedia_like
    return wikipedia_like(n=1 << (scale + (SCALE - 1)), avg_deg=16)


def row(name: str, us_per_call: float, derived: str = "") -> str:
    line = f"{name},{us_per_call:.1f},{derived}"
    print(line, flush=True)
    return line


def timed(fn, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    us = (time.time() - t0) * 1e6
    return out, us
