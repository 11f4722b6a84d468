"""One run of one cell, after the look for the chips: set-up, the
measured window, the comparison with the reference, and the metrics.

Set-up makes the configuration's graph (from its own ``graph_seed``) and
the search keys (from the run's seed), builds the engine once through
the program's ``graph/apps.py`` (the graph is then resident), and warms
up with one query cut to two chunks of supersteps,
which traces and compiles the programs the window runs.  The window is a
closed loop: one search key at a time, each query the program's
``init_state``, ``run`` and a host fetch of the values; no query starts
once ``seconds`` have passed, and the one in flight finishes.  After
the window, every answer is compared with the reference.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import List, Optional

import numpy as np

import queries as querygen
import tracing

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Counts JAX's trace and compile events inside its ``with``."""

    def __init__(self):
        self.counts = dict.fromkeys(COMPILE_EVENTS, 0)

    def _listen(self, event, duration, **_):
        if event in self.counts:
            self.counts[event] += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._listen)


class ChunkRecorder:
    """The program's ``observer=``: keeps each chunk's host times."""

    def __init__(self):
        self.chunks = []

    def on_run_start(self, meta):
        pass

    def on_chunk(self, span):
        self.chunks.append((span.wall_dispatch_s, span.wall_fetch_s,
                            span.wall_account_s, span.n_steps))

    def on_run_end(self, result):
        pass


@dataclasses.dataclass
class Query:
    root: int
    t: tuple                  # perf_counter: start, prepared, run, fetched
    supersteps: int
    edges: int = 0            # Graph500 traversed input edges
    chunks: Optional[list] = None

    @property
    def wall_s(self) -> float:
        return self.t[3] - self.t[0]


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    cell: dict
    config: dict
    setup_s: float
    window_s: float
    queries: List[Query]
    peak_bytes: Optional[int]
    trace: Optional[dict]


def make_graph_input(graph: dict):
    """The program's graph container, holding the generated arrays."""
    from repro.graph.csr import CSR
    return CSR(row_ptr=graph["row_ptr"], col_idx=graph["col_idx"],
               weights=graph.get("weights"), n_cols=graph["n"])


def build_engine(cfg: dict, alg, graph: dict, root: int, chips: int):
    """The engine over the resident graph, as the configuration states
    the modelled machine; execution settings stay at their defaults."""
    from repro.core.tilegrid import TileGrid
    from repro.graph import apps
    grid = TileGrid(cfg["tiles_y"], cfg["tiles_x"],
                    die_ny=cfg["die_tiles_y"], die_nx=cfg["die_tiles_x"],
                    pkg_ny=cfg["package_tiles_y"],
                    pkg_nx=cfg["package_tiles_x"])
    if grid.packages[0] * grid.packages[1] != cfg["packages"]:
        raise ValueError(f"{cfg['name']}: the tiles make "
                         f"{grid.packages} packages, not {cfg['packages']}")
    if cfg["proxy"] != "table2":
        raise ValueError(f"unknown proxy policy {cfg['proxy']!r}")
    proxy = apps.table2_proxy(grid, alg.APP, slots=cfg["proxy_slots"],
                              region_div=cfg["proxy_region_div"])
    kw = dict(oq_cap=cfg["oq_cap"])
    if chips > 1:
        kw.update(chips=chips, backend="shard_map")
    eng, state, _ = apps.engine_and_state(alg.APP, make_graph_input(graph),
                                          grid, proxy=proxy, root=root, **kw)
    return eng, state


def _fetch_values(state, n: int) -> np.ndarray:
    import jax
    return np.asarray(jax.device_get(state["values"]))[:n]


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def window(eng, alg, roots, n: int, seconds: float, trace_dir=None,
           observe: bool = False):
    """The closed loop of queries; returns (queries, answers, window_s).
    With ``trace_dir`` the first whole query is profiled into it."""
    import jax
    from jax.profiler import TraceAnnotation
    done, answers = [], []
    t_w0 = time.perf_counter()
    for i, root in enumerate(roots):
        if time.perf_counter() - t_w0 >= seconds:
            break
        rec = ChunkRecorder() if observe else None
        traced = trace_dir is not None and i == 0
        if traced:
            jax.profiler.start_trace(trace_dir)
        with TraceAnnotation("bench.query"):
            t0 = time.perf_counter()
            with TraceAnnotation("bench.prep"):
                state = alg.query(eng, int(root))
            t1 = time.perf_counter()
            with TraceAnnotation("bench.run"):
                out, res = eng.run(state, observer=rec)
            t2 = time.perf_counter()
            with TraceAnnotation("bench.fetch"):
                values = _fetch_values(out, n)
            t3 = time.perf_counter()
        if traced:
            jax.profiler.stop_trace()
        done.append(Query(root=int(root), t=(t0, t1, t2, t3),
                          supersteps=int(res.supersteps),
                          chunks=rec.chunks if rec else None))
        answers.append(values)
        del state, out
    return done, answers, done[-1].t[3] - t_w0


def check(alg, graph, done, answers) -> dict:
    """Compare every answer with the reference; fills in each query's
    traversed edges.  Returns the numbers compared and the queries that
    failed."""
    mism, failed = 0, 0
    for q, ans in zip(done, answers):
        ref = alg.levels(graph, q.root)
        bad = alg.mismatches(ans, ref)
        mism += bad
        failed += bad > 0
        q.edges = alg.traversed_edges(graph, ans)
    return dict(level_mismatches=mism, failed=failed)


LIMITS = {"level_mismatches": 0}        # exact: PERF.md, "correct"


def run_cell(bench, cell_name: str, seed: int, seconds: float, trace: bool,
             devices, t_start: float, config: Optional[dict] = None,
             log=sys.stderr, build=build_engine) -> dict:
    """Everything after the look for the chips; returns the result.
    ``config`` replaces the cell's configuration and ``build`` the
    engine (the tests' small sizes, the control)."""
    cell = bench.cell(cell_name)
    cfg = config if config is not None else bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    alg = bench.algorithm(cfg["algorithm"])
    graph = bench.graph_generator(cfg["graph"]).generate(cfg)
    roots = querygen.search_keys(graph, mix, seed)
    n = graph["n"]
    t_graph = time.perf_counter()

    eng, state = build(cfg, alg, graph, int(roots[0]), cell["chips"])
    t_build = time.perf_counter()
    ndev = getattr(getattr(eng, "mesh", None), "ndev", 1)
    if ndev != len(devices):
        raise RuntimeError(f"the engine runs on {ndev} devices, the cell "
                           f"on {len(devices)}")
    # warm-up: one query cut to two chunks of supersteps.  The first
    # chunk takes the fresh state, the second the first's outputs, which
    # on a mesh are sharded: two compiled programs on four chips.
    out, _ = eng.run(state, max_supersteps=2 * max(eng.cfg.run_chunk, 1))
    _fetch_values(out, n)
    del state, out
    t_warm = time.perf_counter()
    setup_s = t_warm - t_start
    print(f"setup_s {setup_s} graph {t_graph - t_start} engine "
          f"{t_build - t_graph} warm-up {t_warm - t_build}", file=log)

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        with CompileCounter() as compiles:
            done, answers, window_s = window(eng, alg, roots, n, seconds,
                                             trace_dir=trace_dir,
                                             observe=trace)
        peak = peak_bytes(devices)
        del eng
        gc.collect()
        summary = None
        if trace:
            summary = tracing.summarize(
                tracing.extract(tracing.find_xplane(trace_dir)))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    numbers = check(alg, graph, done, answers)
    run = Run(cell=cell, config=cfg, setup_s=setup_s, window_s=window_s,
              queries=done, peak_bytes=peak, trace=summary)
    metrics = {}
    for m in bench.metrics(cell_name, trace):
        value = bench.reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    print(f"window_traces {compiles.counts[COMPILE_EVENTS[0]]}", file=log)
    print(f"window_compiles {compiles.counts[COMPILE_EVENTS[1]]}", file=log)
    print(f"queries {len(done)} window_s {window_s} supersteps "
          f"{[q.supersteps for q in done]} wall_s "
          f"{[q.wall_s for q in done]}", file=log)
    print(f"peak_bytes {peak}", file=log)
    correct = all(numbers[k] <= lim for k, lim in LIMITS.items())
    result = dict(correct=correct, attempted=len(done),
                  failed=numbers["failed"], metrics=metrics,
                  device=dict(platform=devices[0].platform,
                              kind=devices[0].device_kind,
                              count=len(devices), memory_peak_bytes=peak))
    if summary is not None:
        result["device"]["busy_s"] = (sum(summary["busy_s"])
                                      / len(summary["busy_s"]))
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = dict(device_ops=summary["device_ops"],
                                   idle_gaps=summary["idle_gaps"])
    result["checks"] = {k: {"value": numbers[k], "limit": lim}
                        for k, lim in LIMITS.items()}
    return result
