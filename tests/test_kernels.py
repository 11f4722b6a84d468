"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (per-kernel allclose against ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
pytestmark = pytest.mark.property
from hypothesis import given, settings, strategies as st

from repro.kernels import deliver_fused as _df
from repro.kernels import ops, ref


@pytest.mark.parametrize("n,bins", [(100, 37), (5000, 1000), (1024, 512),
                                    (3000, 2048), (1, 5)])
def test_histogram_shapes(n, bins, rng):
    idx = rng.integers(0, bins, n).astype(np.int32)
    a = ops.histogram(jnp.asarray(idx), bins)
    b = ref.histogram_ref(idx, bins)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_histogram_ignores_padding(rng):
    idx = np.array([-1, 0, 1, -1, 1], np.int32)
    a = ops.histogram(jnp.asarray(idx), 4)
    np.testing.assert_allclose(np.asarray(a), [1, 2, 0, 0])


@pytest.mark.parametrize("combine", ["min", "add"])
@pytest.mark.parametrize("n", [17, 2048, 5000])
def test_relax(combine, n, rng):
    v = rng.random(n).astype(np.float32)
    m = rng.random(n).astype(np.float32)
    f = rng.random(n) < 0.5
    a1, a2 = ops.relax(jnp.asarray(v), jnp.asarray(m), jnp.asarray(f),
                       combine=combine)
    b1, b2 = ref.relax_ref(v, m, f, combine=combine)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(b1), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(a2), np.asarray(b2))


@pytest.mark.parametrize("combine", ["min", "add"])
@pytest.mark.parametrize("n,segs", [(100, 7), (4000, 700), (2048, 513),
                                    (5000, 3000)])
def test_segment_combine(combine, n, segs, rng):
    seg = rng.integers(0, segs, n).astype(np.int32)
    val = rng.random(n).astype(np.float32)
    a = ops.segment_combine(jnp.asarray(seg), jnp.asarray(val), segs,
                            combine=combine)
    b = ref.segment_combine_ref(seg, val, segs, combine=combine)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("combine", ["min", "add"])
def test_segment_combine_presorted(combine, rng):
    """The engine's grouped streams: valid indices ascending, padding
    last, so the kernel skips its sort."""
    segs = 3000
    seg = np.sort(rng.integers(0, segs, 4500)).astype(np.int32)
    seg = np.concatenate([seg, np.full(700, -1, np.int32)])
    val = rng.random(seg.shape[0]).astype(np.float32)
    a = ops.segment_combine(jnp.asarray(seg), jnp.asarray(val), segs,
                            combine=combine, presorted=True)
    b = ref.segment_combine_ref(seg, val, segs, combine=combine)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("combine", ["min", "add"])
def test_deliver_fused(combine, rng):
    """Mailbox and counts against ref.py over several mailbox blocks:
    records cross record-block boundaries, mailbox blocks 1 and 4 get
    none, and out-of-range indices are padding."""
    nd = 5000
    seg = np.concatenate([rng.integers(0, 1000, 2500),
                          rng.integers(2100, 4000, 2500),
                          [-1, nd, nd + 7, -3]]).astype(np.int32)
    rng.shuffle(seg)
    val = rng.random(seg.shape[0]).astype(np.float32)
    mail = rng.random(nd).astype(np.float32)
    out, cnt = ops.deliver_fused(jnp.asarray(seg), jnp.asarray(val),
                                 jnp.asarray(mail), combine=combine)
    valid = np.where((seg >= 0) & (seg < nd), seg, -1)
    part = np.asarray(ref.segment_combine_ref(valid, val, nd, combine))
    want = np.minimum(mail, part) if combine == "min" else mail + part
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(cnt),
                                  np.asarray(ref.histogram_ref(valid, nd)))


def _sorted_keys(rng, n, nd, lo, hi, pad):
    """Destination-sorted keys as the kernel sees them: ``n`` indices in
    [lo, hi) ascending, then ``pad`` padding keys."""
    keys = np.sort(rng.integers(lo, hi, n)).astype(np.int32)
    return np.concatenate([keys, np.full(pad, _df._NO_KEY, np.int32)])


@pytest.mark.parametrize("n,nd,lo,hi,pad", [
    (6000, 5000, 0, 5000, 144),        # every block, several record blocks
    (6000, 5000, 1024, 2048, 144),     # one block spans all records
    (2000, 8192, 0, 9, 48),            # records in block 0, the rest empty
    (0, 3000, 0, 1, 1024),             # padding only
    (1024, 4096, 1023, 1025, 0),       # keys straddle a block edge
])
def test_deliver_work_list(n, nd, lo, hi, pad, rng):
    """The delivery grid's invariants, which the output-window race check
    cannot see: every mailbox block is one contiguous run of live steps,
    FIRST opens it, LAST closes it, and its VALID steps visit exactly
    the record blocks holding its records."""
    blk = _df.ROWS_R * _df.LANES
    skey = _sorted_keys(rng, n, nd, lo, hi, pad)
    n_s = -(-nd // blk)
    s, r, f = (np.asarray(t) for t in
               _df._work_list(jnp.asarray(skey), n_s, blk, blk))
    assert s.shape == (n_s + skey.shape[0] // blk,)
    live = np.flatnonzero(f)
    assert np.array_equal(live, np.arange(live.size))     # inert tail
    for b in range(n_s):
        run = live[s[live] == b]
        assert run.size and np.array_equal(run, np.arange(run[0],
                                                          run[-1] + 1))
        firsts = run[(f[run] & _df._FIRST) != 0]
        lasts = run[(f[run] & _df._LAST) != 0]
        assert list(firsts) == [run[0]] and list(lasts) == [run[-1]]
        mine = np.flatnonzero((skey >= b * blk) & (skey < (b + 1) * blk))
        visited = r[run[(f[run] & _df._VALID) != 0]]
        assert np.array_equal(visited, np.unique(mine // blk))


@pytest.mark.parametrize("bm,bk", [(32, 32), (64, 128)])
def test_spmv_blocks(bm, bk, rng):
    from repro.graph import rmat_edges
    g = rmat_edges(7, edge_factor=6, seed=2)
    mat = ops.bcsr_from_csr(g.row_ptr, g.col_idx, g.weights,
                            (g.n_rows, g.n_cols), bm=bm, bk=bk)
    x = rng.random(g.n_cols).astype(np.float32)
    a = ops.spmv(mat, x)
    b = ref.spmv_ref_csr(g.row_ptr, g.col_idx, g.weights, x)
    np.testing.assert_allclose(np.asarray(a), b, rtol=1e-4, atol=1e-4)


def test_spmv_dense_equivalence(rng):
    """BCSR conversion is lossless: y == dense A @ x."""
    n = 96
    dense = (rng.random((n, n)) < 0.05) * rng.random((n, n))
    rp = np.concatenate([[0], np.cumsum((dense != 0).sum(1))]).astype(np.int64)
    ci = np.nonzero(dense)[1].astype(np.int32)
    w = dense[dense != 0].astype(np.float32)
    mat = ops.bcsr_from_csr(rp, ci, w, (n, n), bm=32, bk=32)
    x = rng.random(n).astype(np.float32)
    np.testing.assert_allclose(np.asarray(ops.spmv(mat, x)),
                               dense.astype(np.float32) @ x,
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,hkv,s,d,block", [
    (2, 8, 2, 300, 64, 128), (1, 4, 4, 64, 32, 64), (3, 6, 3, 1000, 128, 256)])
def test_decode_attention(dtype, b, h, hkv, s, d, block, rng):
    q = rng.standard_normal((b, h, d)).astype(np.float32)
    k = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    v = rng.standard_normal((b, hkv, s, d)).astype(np.float32)
    lens = rng.integers(1, s + 1, b).astype(np.int32)
    out = ops.decode_attention(jnp.asarray(q, dtype), jnp.asarray(k, dtype),
                               jnp.asarray(v, dtype), jnp.asarray(lens),
                               block_s=block)
    want = ref.decode_attention_ref(q, k, v, lens)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


@given(st.integers(1, 300), st.integers(2, 64))
@settings(max_examples=20, deadline=None)
def test_histogram_property(n, bins):
    rng = np.random.default_rng(n * 31 + bins)
    idx = rng.integers(0, bins, n).astype(np.int32)
    a = np.asarray(ops.histogram(jnp.asarray(idx), bins))
    assert a.sum() == n                         # conservation
    np.testing.assert_allclose(a, np.bincount(idx, minlength=bins))
