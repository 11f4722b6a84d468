"""Histogram binning kernel (the paper's Histo app hot loop).

TPU adaptation: instead of per-element scatter (no efficient arbitrary
scatter on the VPU), records are sorted by bin and each (record-block,
bin-block) grid step builds a one-hot membership matrix in VMEM and
reduces over records — the bin update becomes dense vector ops.  This is
the delivery kernel's count output (``deliver_fused``)
with zero-valued records.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .deliver_fused import deliver_fused


def histogram_bin(idx: jax.Array, num_bins: int, interpret: bool = True,
                  **blocks) -> jax.Array:
    """Count occurrences of each bin id.  idx: (N,) int32 in [0, num_bins)
    (negative = padding, ignored).  Returns (num_bins,) float32 counts."""
    # the counts do not depend on the combine; "min" needs no stable
    # record order, so the sort compiles faster
    _, counts = deliver_fused(idx, jnp.zeros(idx.shape, jnp.float32),
                              jnp.zeros((num_bins,), jnp.float32), "min",
                              interpret=interpret, **blocks)
    return counts


def analysis_cases():
    """(name, thunk, combine) case for ``repro.analysis.pallas_races``:
    a multi-record-block invocation whose bin-block windows are revisited
    across record blocks (accumulating add — commutative-safe)."""
    idx = jnp.asarray([0, 5, 5, 2, 7, 0], jnp.int32)
    return [("histogram_bin",
             functools.partial(histogram_bin, idx, 8, rows_r=1, rows_s=1,
                               lanes=2),
             "add")]
