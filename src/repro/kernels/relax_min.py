"""Fused mailbox-drain / relaxation kernel (BFS/SSSP/WCC vertex update).

The engine's IQ drain is: for every owned item, combine the pending
mailbox record into the value array and report whether it improved
(improvements re-activate the item's edge cursor).  One elementwise pass,
fused so values/mailbox/flags stream through VMEM once.  The arrays are
viewed as ``(rows, 128)`` lanes and blocked ``(block_rows, 128)``; the
int8 flags set the row granularity to 32, their TPU tile height.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 512          # 512 x 128 = 64 Ki items per grid step


def _kernel(v_ref, m_ref, f_ref, out_v_ref, out_i_ref, *, combine: str):
    v = v_ref[...]
    m = m_ref[...]
    f = f_ref[...] != 0
    if combine == "min":
        imp = f & (m < v)
        out_v_ref[...] = jnp.where(imp, m, v)
    else:  # add: every flagged record "improves" (accumulates)
        imp = f
        out_v_ref[...] = jnp.where(f, v + m, v)
    out_i_ref[...] = imp.astype(jnp.int8)


def relax(values: jax.Array, mail_val: jax.Array, mail_flag: jax.Array,
          combine: str = "min", block_rows: int = BLOCK_ROWS,
          lanes: int = LANES, interpret: bool = True):
    """Returns (new_values, improved int8 mask)."""
    assert combine in ("min", "add")
    n = values.shape[0]
    rows = -(-n // lanes)
    block_rows = min(block_rows, -(-rows // 32) * 32)
    rows_pad = -(-rows // block_rows) * block_rows
    n_pad = rows_pad * lanes
    ident = jnp.inf if combine == "min" else 0.0

    def pad(a, fill, dt):
        return jnp.concatenate([a.astype(dt), jnp.full((n_pad - n,), fill,
                                                       dt)]) \
            .reshape(rows_pad, lanes)

    v = pad(values, ident, jnp.float32)
    m = pad(mail_val, ident, jnp.float32)
    f = pad(mail_flag, 0, jnp.int8)
    spec = pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))
    out_v, out_i = pl.pallas_call(
        functools.partial(_kernel, combine=combine),
        grid=(rows_pad // block_rows,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((rows_pad, lanes), jnp.float32),
                   jax.ShapeDtypeStruct((rows_pad, lanes), jnp.int8)],
        interpret=interpret,
    )(v, m, f)
    return out_v.reshape(-1)[:n], out_i.reshape(-1)[:n]


def analysis_cases():
    """(name, thunk, combine) cases for ``repro.analysis.pallas_races``.
    The relax kernel is elementwise — each grid program owns a disjoint
    output window — so it is declared ``overwrite``: the race pass must
    prove disjointness rather than rely on combine commutativity."""
    n = 10
    vals = jnp.full((n,), jnp.inf, jnp.float32)
    mail = jnp.arange(n, dtype=jnp.float32)
    flag = jnp.ones((n,), jnp.bool_)
    return [(f"relax:{c}",
             functools.partial(relax, vals, mail, flag, c, block_rows=1,
                               lanes=4),
             "overwrite")
            for c in ("min", "add")]
