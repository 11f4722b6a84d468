"""Public jit'd entry points for the Pallas kernels.

``interpret`` defaults to auto: interpreted on the CPU backend (the
kernel bodies run as plain XLA ops, for bit-faithful validation against
ref.py) and compiled everywhere else.  A backend that cannot compile a
kernel fails loudly instead of falling back to the interpreter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import decode_attention as _da
from . import deliver_fused as _df
from . import histogram_bin as _hb
from . import relax_min as _rx
from . import segment_combine as _sc
from . import spmv_csr as _sp

bcsr_from_csr = _sp.bcsr_from_csr
BCSR = _sp.BCSR


def _auto_interpret(interpret):
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


@functools.partial(jax.jit, static_argnames=("num_bins", "interpret"))
def histogram(idx, num_bins: int, interpret=None):
    return _hb.histogram_bin(idx, num_bins,
                             interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("combine", "interpret"))
def relax(values, mail_val, mail_flag, combine: str = "min", interpret=None):
    return _rx.relax(values, mail_val, mail_flag, combine,
                     interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("num_segments", "combine",
                                             "presorted", "interpret"))
def segment_combine(seg, val, num_segments: int, combine: str = "min",
                    presorted: bool = False, interpret=None):
    return _sc.segment_combine(seg, val, num_segments, combine,
                               presorted=presorted,
                               interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("combine", "interpret"))
def deliver_fused(seg, val, mail_val, combine: str = "min", interpret=None):
    return _df.deliver_fused(seg, val, mail_val, combine,
                             interpret=_auto_interpret(interpret))


def spmv(mat: _sp.BCSR, x, interpret=None):
    """y = A @ x.  (Not jitted at this level: BCSR holds host numpy; the
    pallas_call inside is jit-compiled by JAX on first use.)"""
    return _sp.spmv_bcsr(mat, jnp.asarray(x),
                         interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("scale", "block_s", "interpret"))
def decode_attention(q, k, v, lengths, scale=None, block_s: int = 512,
                     interpret=None):
    return _da.decode_attention(q, k, v, lengths, scale=scale,
                                block_s=block_s,
                                interpret=_auto_interpret(interpret))


def analysis_cases():
    """(name, thunk, combine) cases for ``repro.analysis.pallas_races``
    covering the scalar-prefetch kernels behind this module's entry
    points.  The thunks call the *unjitted* kernel functions so the race
    pass's ``pallas_call`` capture sees the invocation (the jitted
    wrappers above would hide it behind the trace cache).

    ``decode_attention`` is declared ``softmax-carry``: its output window
    is revisited across KV blocks with an order-dependent online-softmax
    rescale, safe only because the TPU grid executes sequentially — the
    race pass reports it, and the finding lives in the committed
    baseline as the documented exception."""
    import numpy as np

    row_ptr = np.array([0, 2, 3, 3, 5, 6, 8], np.int32)
    col_idx = np.array([0, 9, 4, 1, 8, 2, 0, 5], np.int32)
    mat = bcsr_from_csr(row_ptr, col_idx, None, (6, 10), bm=4, bk=8)
    x = jnp.arange(10, dtype=jnp.float32)

    q = jnp.ones((1, 2, 8), jnp.float32)
    k = jnp.ones((1, 1, 6, 8), jnp.float32)
    v = jnp.ones((1, 1, 6, 8), jnp.float32)
    lengths = jnp.array([6], jnp.int32)
    return [
        ("spmv_bcsr",
         functools.partial(_sp.spmv_bcsr, mat, x, interpret=True), "add"),
        ("decode_attention",
         functools.partial(_da.decode_attention, q, k, v, lengths,
                           block_s=4, interpret=True), "softmax-carry"),
    ]
