"""The OQ emit's lane -> slot map (``engine._emit_lanes``) against a
search-and-gather reference: a vmapped ``jnp.searchsorted(side='right')``
over each row's capped prefix, clamped to the last slot, then
``take_along_axis`` of the slot's start and two gathers of the cursor
arrays through the found slot.  ``pos``, ``emit_mask`` and the int32
bits of the lane value must match bit for bit."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import _emit_lanes


def _search_reference(capped, take_v2d, cur_lo2, cur_val2, B):
    rows, Cs = capped.shape
    b_idx = jnp.arange(B, dtype=jnp.int32)
    vslot = jax.vmap(functools.partial(jnp.searchsorted, side="right"),
                     in_axes=(0, None))(capped, b_idx)
    vslot = jnp.minimum(vslot, Cs - 1)
    capped_prev = capped - take_v2d
    offset = b_idx[None, :] - jnp.take_along_axis(capped_prev, vslot, axis=1)
    vglob = vslot + jnp.arange(rows, dtype=jnp.int32)[:, None] * Cs
    pos = cur_lo2.reshape(-1)[vglob] + offset
    emit_mask = b_idx[None, :] < capped[:, -1][:, None]
    return pos, cur_val2.reshape(-1)[vglob], emit_mask


def _rows(Cs, B, rng):
    """Cursor rows as the fronts build them: all-zero tiles, a hub slot
    whose remainder is far above B, sparse and dense rows; cursor values
    with inf, NaN and -0.0."""
    rows = 24
    rem = np.zeros((rows, Cs), np.int64)
    rem[2, Cs // 2] = 50 * B                              # hub, mid-row
    rem[3, 0] = 7 * B                                     # hub, first slot
    rem[3, -1] = 3                                        # ... starves the rest
    rem[4, -1] = B + 1                                    # hub, last slot
    rem[5:12] = rng.integers(0, 3, (7, Cs)) * (rng.random((7, Cs)) < 0.1)
    rem[12:20] = rng.integers(0, 5, (8, Cs))
    rem[20, :] = 1                                        # exactly Cs lanes
    rem[21, : min(B, Cs)] = 1                             # exactly B lanes
    rem[22, -1] = B - 1                                   # one short of B
    # rows 0, 1 and 23 stay all-zero
    capped = np.minimum(np.cumsum(rem, axis=1), B).astype(np.int32)
    take = capped - np.concatenate(
        [np.zeros((rows, 1), np.int32), capped[:, :-1]], axis=1)
    cur_lo = rng.integers(0, 2**31 - 2**20, (rows, Cs)).astype(np.int32)
    cur_val = rng.standard_normal((rows, Cs)).astype(np.float32)
    specials = [np.inf, -0.0, -np.inf, np.nan, 0.0]
    for r in (2, 3, 4, 12, 20):
        cur_val[r, : len(specials)] = specials[: Cs]
        cur_val[r, -1] = -0.0
    cur_val[2, Cs // 2] = np.inf
    return [jnp.asarray(a) for a in (capped, take, cur_lo, cur_val)]


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("Cs,B", [(64, 64), (16, 64), (256, 64), (64, 8)])
def test_emit_lanes_match_the_search(Cs, B):
    args = _rows(Cs, B, np.random.default_rng(Cs * 1000 + B))
    want = _search_reference(*args, B)
    got = jax.jit(_emit_lanes, static_argnums=4)(*args, B)
    for name, w, g in zip(("pos", "lane_val", "emit_mask"), want, got):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        np.testing.assert_array_equal(_bits(g), _bits(w), err_msg=name)
    # the rows exercise every case: empty, a full row, lanes past the total
    mask = np.asarray(got[2])
    assert not mask[0].any() and mask[2].all() and not mask[22, -1]
