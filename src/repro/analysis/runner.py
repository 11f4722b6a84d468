"""Matrix runner: every analysis pass over the app/backend/partition grid.

One :func:`run_all` call produces the :class:`~.findings.Report` that
``scripts/lint_engine.py`` serializes and CI gates on.  The matrix is
the six paper apps x {jnp, pallas} x {monolithic, 4-chip distributed,
4-chip double-buffered} x {dense, compaction=2} (the Pallas kernel
backend is monolithic-only, so its distributed cells are skipped by
construction — see ``distrib.driver``; the ``-db`` cell traces and runs
the deferred boundary-exchange chunk path; the ``-c2`` cells trace the
capacity ladder's bucket switch, which ``jaxprlint`` walks per branch
and ``lint_bucket_coverage`` asserts is actually present):

  * **jaxprlint** traces each cell's chunk-step function (the scanned
    superstep body, boundary exchange included for distributed cells) to
    a ClosedJaxpr and walks it: host-sync primitives, unsafe overwrite
    scatters.  Per cell it also checks the abstract stats dtypes against
    ``engine._EXACT_INT_STATS`` (the 2**24 class) and, per app, the
    jnp-vs-pallas shape/dtype drift of the step output.
  * **invariants** executes each cell on a tiny RMAT graph (scale 7) and
    checks the measured run: counter conservation, trace sanity,
    monotone frontier (min apps), reprice ratio == 1.
  * **pallas_races** proves output-window disjointness for the kernel
    suite (grid-independent: runs once, not per cell).
  * **deadcode** reports unreachable modules (repo-wide: runs once).

Everything runs on tiny inputs — the static passes trace abstractly
(no device compute) and the invariant runs take a few supersteps each.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from . import deadcode, invariants, jaxprlint, pallas_races
from .findings import Finding, Report

APP_NAMES = ("bfs", "sssp", "wcc", "pagerank", "spmv", "histo")
# (backend, chips, double_buffer, compaction): pallas cells are
# monolithic-only (driver constraint); the double-buffer cell lints +
# runs the deferred boundary-exchange chunk fn (distrib.driver
# ._make_chunk's db path); the compaction cells trace the capacity
# ladder's bucket switch (jaxprlint.lint_bucket_coverage asserts every
# pre-traced branch is present and walkable) and run it over the
# invariants graph
MATRIX = (("jnp", 0, False, 0), ("pallas", 0, False, 0),
          ("jnp", 4, False, 0), ("jnp", 4, True, 0),
          ("jnp", 0, False, 2), ("jnp", 4, True, 2))
_SCALE = 7          # tiny RMAT: 128 vertices — a few supersteps per app
_CHUNK_LEN = 4      # scan length for the traced chunk step


def _inputs():
    from ..core.tilegrid import square_grid
    from ..graph import rmat
    g = rmat.rmat_edges(_SCALE, edge_factor=4, seed=2)
    grid = square_grid(16)
    root = int(np.argmax(g.out_degree()))
    bins = max(g.n_rows // 8, 1)
    hv = rmat.histogram_input(g, bins)
    return g, grid, root, bins, hv


def _proxy_for(name, grid):
    from ..graph import apps
    if name == "bfs":
        return None                        # direct routing (Table II)
    if name == "spmv":
        return apps.table2_proxy(grid, "spmv", cascade_levels=1)
    return apps.table2_proxy(grid, name)


def _cell_engine(name, backend, chips, g, grid, root, bins, hv,
                 double_buffer=False, compaction=0):
    """(engine, state, seeds) for one matrix cell (no run executed)."""
    from ..graph import apps
    return apps.engine_and_state(
        name, g, grid, proxy=_proxy_for(name, grid), root=root,
        histo_values=hv, bins=bins, backend=backend,
        chips=chips, oq_cap=16, double_buffer=double_buffer,
        compaction=compaction)


def _chunk_args(eng, state):
    zero = jnp.zeros((), jnp.bool_)
    return (state, zero, zero, jnp.int32(64))


def _lint_cell(name, backend, chips, g, grid, root, bins, hv,
               where: str, double_buffer=False,
               compaction=0) -> List[Finding]:
    """Static passes of one cell: trace the chunk step + int-stat check."""
    import jax
    eng, state, _seeds = _cell_engine(name, backend, chips, g, grid, root,
                                      bins, hv, double_buffer, compaction)
    if chips:
        chunk_fn = eng._get_chunk_fn(_CHUNK_LEN)
        raw = eng._raw_vmap_step()

        def step(st, fl):
            return raw(eng._graph_s, st, eng._chip_ids, fl)
        args = _chunk_args(eng, state)
    else:
        # the graph is an argument of the traced program, as on device
        chunk_fn = functools.partial(eng._chunk_impl, length=_CHUNK_LEN)
        step = eng._chunk_step_one
        args = (eng.graph,) + _chunk_args(eng, state)
    closed = jax.make_jaxpr(chunk_fn)(*args)
    findings = jaxprlint.lint_jaxpr(closed, where)
    if compaction:
        kernel = eng.kernel if chips else eng
        findings += jaxprlint.lint_bucket_coverage(
            closed, len(kernel._ladder), where)
    from ..core.engine import _EXACT_INT_STATS
    shapes = jaxprlint.stats_shapes_of(step, state,
                                       jnp.zeros((), jnp.bool_))
    findings += jaxprlint.lint_int_stats(shapes, _EXACT_INT_STATS, where)
    return findings


def _drift_cell(name, g, grid, root, bins, hv, where: str) -> List[Finding]:
    """jnp-vs-pallas structural drift of one app's step output."""
    import jax
    trees = {}
    for backend in ("jnp", "pallas"):
        eng, state, _ = _cell_engine(name, backend, 0, g, grid, root,
                                     bins, hv)
        trees[backend] = jax.eval_shape(eng._chunk_step_one, state,
                                        jnp.zeros((), jnp.bool_))
    return jaxprlint.lint_backend_drift(trees["jnp"], trees["pallas"],
                                        where)


def _run_cell(name, backend, chips, g, grid, root, bins, hv,
              where: str, double_buffer=False,
              compaction=0) -> List[Finding]:
    """Execute one cell and check the measured run's invariants."""
    from ..graph import apps
    proxy = _proxy_for(name, grid)
    kw = dict(backend=backend, oq_cap=16, double_buffer=double_buffer,
              compaction=compaction)
    if chips:
        kw["chips"] = chips
    if name == "bfs":
        res = apps.bfs(g, root, grid, **kw)
        seeds = 1
    elif name == "sssp":
        res = apps.sssp(g, root, grid, proxy=proxy, **kw)
        seeds = 1
    elif name == "wcc":
        res = apps.wcc(g, grid, proxy=proxy, **kw)
        seeds = g.n_rows
    elif name == "pagerank":
        res = apps.pagerank(g, grid, proxy=proxy, epochs=2, **kw)
        seeds = 0
    elif name == "spmv":
        x = np.random.default_rng(3).random(g.n_cols).astype(np.float32)
        res = apps.spmv(g, x, grid, proxy=proxy, **kw)
        seeds = 0
    elif name == "histo":
        res = apps.histogram(hv, bins, grid, proxy=proxy, **kw)
        seeds = 0
    else:
        raise ValueError(name)
    write_back = proxy is not None and proxy.write_back
    from ..core.costmodel import DCRA_SRAM
    return invariants.check_run(res.run, pkg=DCRA_SRAM, grid=grid,
                                where=where, write_back=write_back,
                                seeds=seeds)


def run_all(repo_root, app_names: Optional[Sequence[str]] = None,
            passes: Optional[Sequence[str]] = None,
            progress=None) -> Report:
    """Run the selected passes over the whole matrix -> :class:`Report`.

    ``passes`` defaults to all of ``("jaxprlint", "invariants",
    "pallas_races", "deadcode")``; ``progress`` is an optional
    ``callable(str)`` for CLI progress lines.
    """
    apps_sel = tuple(app_names or APP_NAMES)
    passes_sel = tuple(passes or ("jaxprlint", "invariants",
                                  "pallas_races", "deadcode"))
    say = progress or (lambda _msg: None)
    report = Report(passes=list(passes_sel))
    g, grid, root, bins, hv = _inputs()

    for name in apps_sel:
        for backend, chips, db, comp in MATRIX:
            part = f"{chips}chips" if chips else "mono"
            if db:
                part += "-db"
            if comp:
                part += f"-c{comp}"
            where = f"{name}/{backend}/{part}"
            report.matrix.append(where)
            if "jaxprlint" in passes_sel:
                say(f"jaxprlint {where}")
                report.extend(_lint_cell(name, backend, chips, g, grid,
                                         root, bins, hv, where, db, comp))
            if "invariants" in passes_sel:
                say(f"invariants {where}")
                report.extend(_run_cell(name, backend, chips, g, grid,
                                        root, bins, hv, where, db, comp))
        if "jaxprlint" in passes_sel:
            say(f"backend-drift {name}")
            report.extend(_drift_cell(name, g, grid, root, bins, hv,
                                      f"{name}/drift"))

    if "pallas_races" in passes_sel:
        say("pallas_races kernel suite")
        report.extend(pallas_races.check_kernels())
    if "deadcode" in passes_sel:
        say("deadcode import graph")
        dc, _meta = deadcode.check_repo(repo_root)
        report.extend(dc)
    return report
