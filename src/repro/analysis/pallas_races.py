"""Pallas write-race detector: output-window disjointness per grid program.

For every ``pl.pallas_call`` a kernel entry point issues, this pass
evaluates the output ``BlockSpec`` index maps symbolically over the whole
grid (index maps are pure functions of the grid coordinates — calling
them with python ints costs nothing) and computes each program's output
element windows (``index_map(*program) * block_shape``).  Two distinct
grid programs mapping to the same window are *aliased writes*:

  * with a commutative combine ("add"/"min"/"max") and the revisit
    idiom (``@pl.when(first_visit)`` init + in-place accumulation) they
    are the standard Pallas reduction pattern — safe, because the TPU
    grid executes sequentially, so revisits are ordered;
  * with overwrite semantics they are a bug: the last program in grid
    order silently wins (and on a parallel backend the result is
    non-deterministic).  The pass rejects them.

Partially overlapping windows (possible only with element-indexed
maps / misaligned blocking) are rejected unconditionally.  So are a
window revisited by two separate runs of grid programs (the TPU writes
an output block back when the block index changes, so a second run
starts from a stale buffer and overwrites the first run's result) and
an output window no program writes (it is left uninitialised).

Index maps that read scalar-prefetch tables (work lists, the BCSR
column table) are evaluated against the tables the call was actually
given: the capture records them, so a data-driven grid is checked on
the windows it really visits.  A table that was not concrete at
capture time (the call sat under a ``jit`` trace) is reported, since
its windows cannot be evaluated.

Calls are captured by temporarily wrapping ``pallas.pallas_call`` while
invoking the kernel entry point on tiny inputs (``capture_pallas_calls``)
— the kernel modules need no modification, and the capture also serves
as a smoke execution of the kernel.  Each kernel module exports
``analysis_cases()`` returning (name, thunk, combine) triples so the
suite enumerates itself (``kernel_suite``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.experimental import pallas as pl

from .findings import Finding

COMMUTATIVE = ("add", "min", "max")


@dataclasses.dataclass
class CapturedCall:
    """One ``pl.pallas_call`` invocation's static geometry."""

    kernel_name: str
    grid: Tuple[int, ...]
    out_specs: List[object]           # normalized to a list of BlockSpec
    out_shapes: List[Tuple[int, ...]]
    n_prefetch: int = 0               # scalar-prefetch args the index maps take
    prefetch: Optional[Tuple[np.ndarray, ...]] = None   # their values


def _kernel_name(kernel) -> str:
    """Stable name for a kernel callable (unwraps functools.partial — a
    repr would embed a memory address and churn baseline keys)."""
    inner = getattr(kernel, "func", kernel)
    return getattr(inner, "__name__", type(kernel).__name__)


@contextlib.contextmanager
def capture_pallas_calls():
    """Capture every ``pl.pallas_call`` issued inside the block (the call
    still executes normally).  Yields the list the captures append to."""
    captured: List[CapturedCall] = []
    real = pl.pallas_call

    def wrapper(kernel, **kw):
        grid_spec = kw.get("grid_spec")
        if grid_spec is not None:     # PrefetchScalarGridSpec form
            grid = grid_spec.grid
            out_specs = grid_spec.out_specs
            n_prefetch = int(getattr(grid_spec, "num_scalar_prefetch", 0))
        else:
            grid = kw.get("grid", ())
            out_specs = kw.get("out_specs")
            n_prefetch = 0
        if isinstance(grid, int):
            grid = (grid,)
        out_shape = kw.get("out_shape")
        specs = list(out_specs) if isinstance(out_specs, (list, tuple)) \
            else [out_specs]
        shapes = out_shape if isinstance(out_shape, (list, tuple)) \
            else [out_shape]
        call = CapturedCall(
            kernel_name=_kernel_name(kernel),
            grid=tuple(int(g) for g in grid),
            out_specs=specs,
            out_shapes=[tuple(s.shape) for s in shapes],
            n_prefetch=n_prefetch)
        captured.append(call)
        run = real(kernel, **kw)

        def invoke(*args):
            tables = args[:n_prefetch]
            if not any(isinstance(t, jax.core.Tracer) for t in tables):
                call.prefetch = tuple(np.asarray(t) for t in tables)
            return run(*args)
        return invoke

    pl.pallas_call = wrapper
    try:
        yield captured
    finally:
        pl.pallas_call = real


def _program_windows(call: CapturedCall, spec) -> Dict[Tuple, List[int]]:
    """window -> grid programs writing it, as positions in grid order
    (row-major: the order the TPU runs them).  A window is a tuple of
    per-dim (start, stop) element ranges: ``index_map`` returns block
    indices, scaled by ``block_shape`` (the installed Pallas convention —
    see e.g. ``kernels/relax_min.py``)."""
    block = tuple(int(b) for b in spec.block_shape)
    ranges = [range(max(int(g), 1)) for g in call.grid] or [range(1)]
    tables = call.prefetch or ()
    windows: Dict[Tuple, List[int]] = {}
    for pos, program in enumerate(itertools.product(*ranges)):
        idx = spec.index_map(*program, *tables)
        if not isinstance(idx, tuple):
            idx = (idx,)
        win = tuple((int(i) * b, (int(i) + 1) * b)
                    for i, b in zip(idx, block))
        windows.setdefault(win, []).append(pos)
    return windows


def _all_windows(shape: Tuple[int, ...], spec) -> set:
    """Every block window tiling an output of ``shape``."""
    block = tuple(int(b) for b in spec.block_shape)
    counts = [-(-int(n) // b) for n, b in zip(shape, block)]
    return {tuple((i * b, (i + 1) * b) for i, b in zip(idx, block))
            for idx in itertools.product(*(range(c) for c in counts))}


def _windows_overlap(a: Tuple, b: Tuple) -> bool:
    return all(lo1 < hi2 and lo2 < hi1
               for (lo1, hi1), (lo2, hi2) in zip(a, b))


def check_call(call: CapturedCall, combine: str, where: str) -> List[Finding]:
    """Race-check one captured call under the declared combine semantics
    (``'add' | 'min' | 'max'`` commutative accumulation, anything else —
    canonically ``'overwrite'`` — order-sensitive)."""
    findings = []
    commutative = combine in COMMUTATIVE
    site = f"{where}:{call.kernel_name}"
    if call.n_prefetch and call.prefetch is None:
        return [Finding(
            "pallas_races", "prefetch-unknown", site,
            f"index maps read {call.n_prefetch} scalar-prefetch table(s) "
            f"that were not concrete at capture (traced under jit?): the "
            f"output windows cannot be evaluated")]
    for out_i, (spec, shape) in enumerate(zip(call.out_specs,
                                              call.out_shapes)):
        windows = _program_windows(call, spec)
        site = f"{where}:{call.kernel_name}[out{out_i}]"
        unwritten = sorted(_all_windows(shape, spec) - set(windows))
        if unwritten:
            findings.append(Finding(
                "pallas_races", "unwritten-window", site,
                f"{len(unwritten)} output window(s) written by no grid "
                f"program (e.g. {unwritten[0]}): left uninitialised"))
        split = {w: ps for w, ps in windows.items()
                 if ps[-1] - ps[0] + 1 != len(ps)}
        if split:
            w, ps = next(iter(sorted(split.items())))
            findings.append(Finding(
                "pallas_races", "split-revisit", site,
                f"{len(split)} output window(s) revisited by separate runs "
                f"of grid programs (e.g. window {w} at grid positions "
                f"{ps[:6]}): a later run overwrites the earlier one's "
                f"written-back block"))
        # aliased writes: >1 program revisits one window
        aliased = {w: ps for w, ps in windows.items() if len(ps) > 1}
        if aliased and not commutative:
            w, ps = next(iter(sorted(aliased.items())))
            findings.append(Finding(
                "pallas_races", "aliased-overwrite", site,
                f"{len(aliased)} output window(s) written by multiple grid "
                f"programs (e.g. window {w} at grid positions {ps[:4]}) with "
                f"non-commutative combine '{combine}': last program in "
                f"grid order wins silently"))
        # partial overlap between distinct windows: always wrong
        keys = sorted(windows)
        for i, w1 in enumerate(keys):
            for w2 in keys[i + 1:]:
                if _windows_overlap(w1, w2):
                    findings.append(Finding(
                        "pallas_races", "window-overlap", site,
                        f"output windows {w1} (grid positions "
                        f"{windows[w1][:2]}) and {w2} (grid positions "
                        f"{windows[w2][:2]}) partially overlap: "
                        f"misaligned blocking races regardless of the "
                        f"combine"))
    return findings


def check_fn(thunk, combine: str, where: str) -> List[Finding]:
    """Run ``thunk`` (a kernel invocation on tiny inputs) under capture
    and race-check every pallas_call it issued."""
    with capture_pallas_calls() as calls:
        thunk()
    findings = []
    if not calls:
        findings.append(Finding(
            "pallas_races", "no-pallas-call", where,
            "kernel thunk issued no pallas_call: the race check is "
            "vacuous (did the entry point hit a cached jit?)"))
    for call in calls:
        findings.extend(check_call(call, combine, where))
    return findings


def kernel_suite() -> List[Tuple[str, object, str]]:
    """(name, thunk, combine) for every analyzable kernel in
    ``repro.kernels`` — collected from each module's ``analysis_cases``."""
    from ..kernels import (deliver_fused, histogram_bin, ops, relax_min,
                           segment_combine)
    cases = []
    for mod in (segment_combine, relax_min, histogram_bin, deliver_fused,
                ops):
        cases.extend(mod.analysis_cases())
    return cases


def check_kernels() -> List[Finding]:
    """Race-check the whole kernel suite (the ops-level entry points'
    underlying pallas_calls)."""
    findings = []
    for name, thunk, combine in kernel_suite():
        findings.extend(check_fn(thunk, combine, f"kernels/{name}"))
    return findings
