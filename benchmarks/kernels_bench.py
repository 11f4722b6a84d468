"""Kernel micro-benchmarks (interpret-mode timings are NOT TPU perf —
they validate plumbing; the structural figure of merit is bytes/FLOPs per
block from the BlockSpec tiling, reported as derived columns)."""
from __future__ import annotations

import numpy as np

from common import row, timed

import jax.numpy as jnp

from repro.kernels import deliver_fused as df
from repro.kernels import ops
from repro.kernels import relax_min as rx


def run(small: bool = True):
    rng = np.random.default_rng(0)
    n = 1 << 14
    bins = 1024
    idx = jnp.asarray(rng.integers(0, bins, n).astype(np.int32))
    _, us = timed(lambda: np.asarray(ops.histogram(idx, bins)))
    # VMEM per grid step: six (8, 128) f32/int32 blocks (record keys and
    # values, mailbox in and out, counts, accumulator) plus one
    # (128 records x 128 slots) one-hot hit tile
    tile = df.ROWS_R * df.LANES * 4
    row("kernels/histogram", us,
        f"n={n};bins={bins};vmem_block_bytes={6 * tile};"
        f"hit_tile_bytes={df.LANES * df.LANES * 4}")

    v = jnp.asarray(rng.random(n).astype(np.float32))
    m = jnp.asarray(rng.random(n).astype(np.float32))
    f = jnp.asarray(rng.random(n) < 0.5)
    _, us = timed(lambda: [np.asarray(x) for x in
                           ops.relax(v, m, f, combine="min")])
    row("kernels/relax_min", us,
        f"n={n};f32_block_bytes={rx.BLOCK_ROWS * rx.LANES * 4}")

    seg = jnp.asarray(rng.integers(0, 512, n).astype(np.int32))
    _, us = timed(lambda: np.asarray(
        ops.segment_combine(seg, v, 512, combine="add")))
    row("kernels/segment_combine", us, f"n={n};segments=512")

    from repro.graph import rmat_edges
    g = rmat_edges(9, edge_factor=8, seed=3)
    mat = ops.bcsr_from_csr(g.row_ptr, g.col_idx, g.weights,
                            (g.n_rows, g.n_cols), bm=64, bk=64)
    x = jnp.asarray(rng.random(g.n_cols).astype(np.float32))
    _, us = timed(lambda: np.asarray(ops.spmv(mat, x)))
    density = g.nnz / (g.n_rows * g.n_cols)
    row("kernels/spmv_bcsr", us,
        f"nnz={g.nnz};kmax={mat.kmax};density={density:.4f};"
        f"mxu_tile=64x64")

    b, h, hkv, s, d = 2, 8, 2, 2048, 64
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.bfloat16)
    vv = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.bfloat16)
    lens = jnp.full((b,), s, jnp.int32)
    _, us = timed(lambda: np.asarray(
        ops.decode_attention(q, k, vv, lens, block_s=512)))
    row("kernels/decode_attention", us,
        f"S={s};kv_block_bytes={512*d*2*2};flash_decode=1")
    return True


if __name__ == "__main__":
    run()
