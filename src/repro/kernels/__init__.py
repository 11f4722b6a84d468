"""Pallas TPU kernels for the framework's compute hot-spots.

spmv_csr        block-sparse (BCSR) SpMV — the paper's SPMV app, re-tiled
                for the MXU with scalar-prefetch dynamic x-block gather.
deliver_fused   owner delivery: a destination-sorted record stream
                combined into the mailbox (min/add) plus arrival counts,
                one scalar-prefetched work list of (mailbox, record)
                blocks per launch.
histogram_bin   binning — the delivery kernel's counts.
relax_min       fused mailbox drain (min/add combine + improved mask) —
                the vertex-update task of BFS/SSSP/WCC.
segment_combine segment min/add reduction — the proxy (P$)
                filter/coalesce operation itself (the delivery kernel
                against an identity mailbox).
decode_attention flash-decode GQA attention — the serving-side hot spot.

Each kernel is a pl.pallas_call with explicit BlockSpec VMEM tiling,
validated in interpret mode against the pure-jnp oracles in ref.py.
"""
from . import ops, ref

__all__ = ["ops", "ref"]
