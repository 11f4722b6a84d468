"""Segment combine kernel — the proxy (P$) coalescing operation itself.

The paper's proxy tile merges all same-destination updates arriving in a
region (min for SSSP/BFS/WCC, add for PageRank/SPMV/Histo) before
forwarding one combined record to the owner.  On TPU the proxy store is a
dense regional buffer; combining a batch of (segment_id, value) records
into it is a dense segment reduction — the delivery kernel
(``deliver_fused``) run against an identity mailbox.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .deliver_fused import deliver_fused


def segment_combine(seg: jax.Array, val: jax.Array, num_segments: int,
                    combine: str = "min", presorted: bool = False,
                    interpret: bool = True, **blocks) -> jax.Array:
    """Dense segment reduction.  seg: (N,) int32 in [0, num_segments)
    (negative = padding); val: (N,) float32.  Returns (num_segments,)
    combined values; untouched segments get the combine identity
    (+inf for min, 0 for add).  ``presorted``: valid ``seg`` ascending,
    padding after it (the engine's grouped record streams)."""
    ident = jnp.inf if combine == "min" else 0.0
    out, _ = deliver_fused(seg, val,
                           jnp.full((num_segments,), ident, jnp.float32),
                           combine, presorted=presorted,
                           interpret=interpret, **blocks)
    return out


def analysis_cases():
    """(name, thunk, combine) cases for ``repro.analysis.pallas_races``:
    tiny multi-block invocations whose grid revisits each output
    segment-block across record blocks (the reduction idiom the race
    pass must accept for commutative combines)."""
    seg = jnp.asarray([0, 3, 3, 7, 1, 0], jnp.int32)
    val = jnp.arange(6, dtype=jnp.float32)
    # compacted segment window: shorter record stream with dropped-lane
    # sentinels interleaved (what the engine's active-set compaction
    # branches produce), still multi-block over the record dim
    wseg = jnp.asarray([4, -1, 0, 4, -1, 6], jnp.int32)
    wval = jnp.arange(6, dtype=jnp.float32) + 0.5
    tiny = dict(rows_r=1, rows_s=1, lanes=2)
    return ([(f"segment_combine:{c}",
              functools.partial(segment_combine, seg, val, 8, c, **tiny),
              c)
             for c in ("min", "add")]
            + [(f"segment_combine:compact:{c}",
                functools.partial(segment_combine, wseg, wval, 8, c,
                                  **tiny),
                c)
               for c in ("min", "add")])
