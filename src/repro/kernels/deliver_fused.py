"""Fused owner-delivery kernel — combine a record stream into a mailbox.

One launch reads the record stream once and produces both the relaxed
mailbox *and* the per-index arrival counts; presence and the per-tile
endpoint contention fall out of the counts outside the kernel (mailbox
indices of one tile are contiguous, so per-tile delivered records are a
reshape-sum; counts are integer-valued, so the derived flags are
bit-identical to a histogram).  ``segment_combine`` and
``histogram_bin`` are this kernel with an identity mailbox.

Kernel shape.  The records are sorted by destination first (one XLA
sort), so each mailbox block receives a contiguous run of records.
A work list of (mailbox block, record block) pairs — every mailbox block
once, plus one pair per extra record block its run spans, at most
``mailbox blocks + record blocks`` in all — is built in jnp and handed
to the kernel through scalar prefetch: the index maps read it, so the
pipeline DMAs exactly the blocks each grid step needs.  Work is
O(records + mailbox) blocks, not the O(records x mailbox) of a dense
one-hot sweep, which at a 2**22-entry mailbox would not finish.

Layout.  Every array is viewed as ``(rows, 128)`` lanes and blocked
``(8, 128)``-aligned, the TPU's f32/int32 tile.  Inside a grid step each
128-record row is broadcast down the sublanes and transposed, so every
record owns one sublane row; a compare against a lane iota then gives
the ``(128 records, 128 slots)`` one-hot hit matrix, and reducing over
sublanes lands the result lane-major, in the mailbox's own layout.

Each mailbox block is visited by one contiguous run of grid steps: the
first step of the run resets an accumulator (the combine identity), the
last folds it into the mailbox block (``mail + sum`` / ``min(mail,
min)``, the jnp path's own association).  Revisits within a run commute
with the combine.  ``analysis.pallas_races`` evaluates the index maps on
the work lists of :func:`analysis_cases` and proves that every mailbox
block is written, each by one contiguous run; ``tests/test_kernels.py``
checks the flags (FIRST opens a run, LAST closes it, VALID steps visit
exactly the block's record blocks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ROWS_R = 8       # record rows per block  (8 x 128 = 1024 records)
ROWS_S = 8       # mailbox rows per block (8 x 128 = 1024 slots)

_FIRST, _VALID, _LAST = 1, 2, 4
_NO_KEY = 2**31 - 1      # sort key of padding records (after every index)


def _kernel(s_tab, r_tab, f_tab, seg_ref, val_ref, mail_ref, out_ref,
            cnt_ref, acc_ref, *, combine: str, lanes: int):
    i = pl.program_id(0)
    flag = f_tab[i]
    ident = float("inf") if combine == "min" else 0.0
    rows_r = seg_ref.shape[0]
    rows_s = out_ref.shape[0]

    @pl.when((flag & _FIRST) != 0)
    def _init():
        acc_ref[...] = jnp.full(acc_ref.shape, ident, jnp.float32)
        cnt_ref[...] = jnp.zeros(cnt_ref.shape, jnp.float32)

    @pl.when((flag & _VALID) != 0)
    def _fold():
        first_slot = s_tab[i] * (rows_s * lanes)
        lane = jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 1)

        def record_row(j, carry):
            # record j*lanes + a -> sublane row a, replicated across lanes
            seg = jnp.transpose(jnp.broadcast_to(
                seg_ref[pl.ds(j, 1), :] - first_slot, (lanes, lanes)))
            val = jnp.transpose(jnp.broadcast_to(
                val_ref[pl.ds(j, 1), :], (lanes, lanes)))
            for k in range(rows_s):
                hit = seg == lane + k * lanes
                cnt_ref[k:k + 1, :] += jnp.sum(
                    hit.astype(jnp.float32), axis=0, keepdims=True)
                if combine == "min":
                    part = jnp.min(jnp.where(hit, val, ident), axis=0,
                                   keepdims=True)
                    acc_ref[k:k + 1, :] = jnp.minimum(acc_ref[k:k + 1, :],
                                                      part)
                else:
                    acc_ref[k:k + 1, :] += jnp.sum(
                        jnp.where(hit, val, 0.0), axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, rows_r, record_row, 0)

    @pl.when((flag & _LAST) != 0)
    def _emit():
        if combine == "min":
            out_ref[...] = jnp.minimum(mail_ref[...], acc_ref[...])
        else:
            out_ref[...] = mail_ref[...] + acc_ref[...]


def _work_list(skey, n_s_blocks: int, blk_r: int, blk_s: int):
    """(s_tab, r_tab, f_tab): the grid's (mailbox block, record block,
    flags) pairs over the destination-sorted keys ``skey``.  Every
    mailbox block gets at least one pair (its accumulator must be
    emitted); a block whose records span several record blocks gets one
    pair per block.  The list is padded to its static bound with repeats
    of the final pair, flagged inert."""
    n_r_blocks = skey.shape[0] // blk_r
    g = n_s_blocks + n_r_blocks
    starts = jnp.arange(n_s_blocks + 1, dtype=jnp.int32) * blk_s
    edges = jnp.searchsorted(skey, starts, side="left").astype(jnp.int32)
    lo, hi = edges[:-1], edges[1:]
    has = hi > lo
    r0 = jnp.minimum(lo // blk_r, n_r_blocks - 1)
    r1 = jnp.where(has, (hi - 1) // blk_r, r0)
    count = r1 - r0 + 1
    ends = jnp.cumsum(count)
    item = jnp.arange(g, dtype=jnp.int32)
    s = jnp.minimum(jnp.searchsorted(ends, item, side="right"),
                    n_s_blocks - 1).astype(jnp.int32)
    start = ends[s] - count[s]
    r = jnp.minimum(r0[s] + item - start, r1[s])
    live = item < ends[-1]
    first = live & (item == start)
    last = live & (item == ends[s] - 1)
    valid = live & has[s]
    flags = (first.astype(jnp.int32) * _FIRST
             + valid.astype(jnp.int32) * _VALID
             + last.astype(jnp.int32) * _LAST)
    return s, r, flags


def deliver_fused(seg: jax.Array, val: jax.Array, mail_val: jax.Array,
                  combine: str = "min", presorted: bool = False,
                  rows_r: int = ROWS_R, rows_s: int = ROWS_S,
                  lanes: int = LANES, interpret: bool = True):
    """Fused mailbox delivery.  seg: (N,) int32 mailbox indices in
    [0, Nd) (anything else = padding); val: (N,) float32; mail_val: (Nd,)
    current mailbox.  Returns ``(new_mail_val, counts)`` — the mailbox
    with every record combined in (min relax / add accumulate) and the
    float32 per-index arrival counts (``counts > 0`` is the flag update;
    a tile-contiguous reshape-sum is the endpoint contention).
    ``presorted`` skips the sort for callers whose valid ``seg`` already
    ascends with all padding after it."""
    assert combine in ("min", "add")
    n, nd = seg.shape[0], mail_val.shape[0]
    blk_r, blk_s = rows_r * lanes, rows_s * lanes
    n_pad = max(-(-n // blk_r), 1) * blk_r
    s_pad = max(-(-nd // blk_s), 1) * blk_s
    seg = seg.astype(jnp.int32)
    key = jnp.where((seg >= 0) & (seg < nd), seg, _NO_KEY)
    skey, sval = key, val.astype(jnp.float32)
    if not presorted:
        # add sums must see a fixed record order (stable); min is
        # order-free and the TPU compiles an unstable sort ~3x faster
        skey, sval = jax.lax.sort((skey, sval), num_keys=1,
                                  is_stable=combine == "add")
    skey = jnp.concatenate([skey, jnp.full((n_pad - n,), _NO_KEY,
                                           jnp.int32)])
    sval = jnp.concatenate([sval, jnp.zeros((n_pad - n,), jnp.float32)])
    mail = jnp.concatenate([mail_val.astype(jnp.float32),
                            jnp.zeros((s_pad - nd,), jnp.float32)])
    n_s_blocks = s_pad // blk_s
    s_tab, r_tab, f_tab = _work_list(skey, n_s_blocks, blk_r, blk_s)
    rec = pl.BlockSpec((rows_r, lanes), lambda i, s, r, f: (r[i], 0))
    box = pl.BlockSpec((rows_s, lanes), lambda i, s, r, f: (s[i], 0))
    out, cnt = pl.pallas_call(
        functools.partial(_kernel, combine=combine, lanes=lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s_tab.shape[0],),
            in_specs=[rec, rec, box],
            out_specs=[box, box],
            scratch_shapes=[pltpu.VMEM((rows_s, lanes), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((s_pad // lanes, lanes),
                                        jnp.float32)] * 2,
        interpret=interpret,
    )(s_tab, r_tab, f_tab, skey.reshape(-1, lanes),
      sval.reshape(-1, lanes), mail.reshape(-1, lanes))
    return out.reshape(-1)[:nd], cnt.reshape(-1)[:nd]


def analysis_cases():
    """(name, thunk, combine) cases for ``repro.analysis.pallas_races``:
    tiny multi-block invocations revisiting each mailbox block across
    record blocks.  Both outputs of a case are reduced with the declared
    combine (the min fold commutes across record blocks; the count
    output is an add either way)."""
    seg = jnp.asarray([0, 3, 3, 7, 1, 0], jnp.int32)
    val = jnp.arange(6, dtype=jnp.float32)
    mail = jnp.full((8,), jnp.inf, jnp.float32).at[1].set(0.5)
    # compacted segment window: the record stream the engine's
    # active-set branches hand the kernel — shorter than the mailbox,
    # with dropped-lane sentinels (-1) interleaved mid-stream, still
    # spanning multiple record blocks so the revisit reduction is
    # exercised at the compacted shape too
    wseg = jnp.asarray([2, -1, 5, 2, -1, 1], jnp.int32)
    wval = jnp.arange(6, dtype=jnp.float32) + 0.25
    tiny = dict(rows_r=1, rows_s=1, lanes=2)
    cases = [(f"deliver_fused:{c}",
              functools.partial(deliver_fused, seg, val,
                                jnp.zeros((8,), jnp.float32) if c == "add"
                                else mail, c, **tiny),
              c)
             for c in ("min", "add")]
    cases += [(f"deliver_fused:compact:{c}",
               functools.partial(deliver_fused, wseg, wval,
                                 jnp.zeros((8,), jnp.float32) if c == "add"
                                 else mail, c, **tiny),
               c)
              for c in ("min", "add")]
    return cases
