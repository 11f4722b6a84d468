"""Telemetry/observability stack (``repro.obs``).

Acceptance properties:
  * attaching an observer (with ``telemetry=True``) is **bit-identical**
    to a bare run — same final values, TrafficCounters, SuperstepTrace
    and superstep count — for all six apps, monolithic and 4-chip, and
    the measured host-sync count (``engine.host_syncs``) is unchanged;
  * the Chrome trace-event export is valid JSON with the documented
    shape and a span for every chunk on every wall track;
  * the imbalance metrics match an O(n²) NumPy oracle on hand-built
    matrices;
  * cascading improves measured load balance: cascade-on total Gini ≤
    cascade-off on the RMAT test graph (8x8 tiles, 4 chips), with
    positive cascade efficacy vs the no-proxy baseline;
  * the metrics registry is deterministic and survives snapshot/reset.
"""
import glob
import json
import os
import re

import numpy as np
import pytest

from repro import obs
from repro.core.tilegrid import square_grid
from repro.graph import apps, rmat_edges
from repro.graph.rmat import histogram_input
from repro.obs import export as obs_export
from repro.obs import imbalance as obs_imbalance
from repro.obs import report as obs_report
from repro.obs.metrics import MetricsRegistry, default_registry

from _subproc import run_devices

GRID = square_grid(16)
CHUNK = 8
ALL_APPS = ("bfs", "sssp", "wcc", "pagerank", "spmv", "histo")


@pytest.fixture(scope="module")
def g():
    return rmat_edges(8, edge_factor=8, seed=1)


@pytest.fixture(scope="module")
def root(g):
    return int(np.argmax(g.out_degree()))


def _run(name, g, root, chips=0, **extra):
    """One chunked run per app, Table-II proxy policy (as test_chunked)."""
    if chips:
        extra["chips"] = chips
    if name == "bfs":
        return apps.bfs(g, root, GRID, oq_cap=16, run_chunk=CHUNK, **extra)
    if name == "sssp":
        px = apps.table2_proxy(GRID, "sssp")
        return apps.sssp(g, root, GRID, proxy=px, oq_cap=16,
                         run_chunk=CHUNK, **extra)
    if name == "wcc":
        px = apps.table2_proxy(GRID, "wcc")
        return apps.wcc(g, GRID, proxy=px, oq_cap=16, run_chunk=CHUNK,
                        **extra)
    if name == "pagerank":
        px = apps.table2_proxy(GRID, "pagerank")
        return apps.pagerank(g, GRID, proxy=px, epochs=2, oq_cap=16,
                             run_chunk=CHUNK, **extra)
    if name == "spmv":
        x = np.random.default_rng(3).random(g.n_cols).astype(np.float32)
        px = apps.table2_proxy(GRID, "spmv", cascade_levels=1)
        return apps.spmv(g, x, GRID, proxy=px, oq_cap=16, run_chunk=CHUNK,
                         **extra)
    if name == "histo":
        bins = g.n_rows // 8
        hv = histogram_input(g, bins)
        px = apps.table2_proxy(GRID, "histo")
        return apps.histogram(hv, bins, GRID, proxy=px, oq_cap=8,
                              run_chunk=CHUNK, **extra)
    raise ValueError(name)


def _syncs() -> float:
    return default_registry().counter("engine.host_syncs").value


# -------------------------------------------------- observer bit-identity
def _assert_observer_inert(name, g, root, chips):
    s0 = _syncs()
    base = _run(name, g, root, chips=chips)
    syncs_off = _syncs() - s0
    rec = obs.TimelineRecorder()
    s1 = _syncs()
    r = _run(name, g, root, chips=chips, telemetry=True, observer=rec)
    syncs_on = _syncs() - s1
    assert np.array_equal(base.values, r.values)
    db, dr = base.run.counters.as_dict(), r.run.counters.as_dict()
    assert db == dr, {k: (db[k], dr[k]) for k in db if db[k] != dr[k]}
    assert base.run.trace.to_dict() == r.run.trace.to_dict()
    assert base.run.supersteps == r.run.supersteps
    assert syncs_on == syncs_off, "observer added host syncs"
    assert rec.spans, "observer saw no chunks"
    assert rec.meta is not None and rec.result is not None
    assert rec.meta.telemetry and rec.meta.chunk == CHUNK
    if name != "pagerank":            # pagerank: one span set per epoch
        assert rec.supersteps == r.run.supersteps
    assert rec.vec_keys(), "telemetry recorded no load vectors"
    return rec, r


@pytest.mark.parametrize("name", ALL_APPS)
def test_observer_bit_identical_monolithic(name, g, root):
    rec, _ = _assert_observer_inert(name, g, root, chips=0)
    assert "tv_delivered" in rec.vec_keys()
    load = obs.run_load_matrix(rec)
    assert load.shape[1] == GRID.ny * GRID.nx


@pytest.mark.parametrize("name", ALL_APPS)
def test_observer_bit_identical_4chip(name, g, root):
    rec, _ = _assert_observer_inert(name, g, root, chips=4)
    assert "pc_delivered" in rec.vec_keys()
    load = obs.run_load_matrix(rec)
    assert load.shape[1] == 4


def test_legacy_loop_emits_per_step_spans(g, root):
    rec = obs.TimelineRecorder()
    r = apps.bfs(g, root, GRID, oq_cap=16, run_chunk=0, telemetry=True,
                 observer=rec)
    assert len(rec.spans) == r.run.supersteps
    assert all(s.n_steps == 1 for s in rec.spans)
    assert rec.supersteps == r.run.supersteps


# ------------------------------------------- profiler spans and phase scopes
# the superstep phase scopes the benchmark's trace reduction reads
# (bench/phases.py), innermost first on an operation's scope path
PHASES = ("front", "proxy", "delivery", "charge")


def _engine_spans(log_dir):
    """{span name: [chunk argument, ...]} of the ``engine.*`` host spans
    in the one profiler trace under ``log_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("engine."):
                    spans.setdefault(e.name, []).append(
                        dict(e.stats).get("chunk"))
    return spans


@pytest.mark.parametrize("chips", [0, 4])
def test_profiler_trace_holds_engine_spans(g, root, chips, tmp_path):
    """Under an active profiler every chunk the observer sees is one
    ``engine.dispatch`` / ``fetch`` / ``account`` span with the chunk's
    index, and the run stays bit-identical to one with no profiler."""
    import jax
    base = _run("bfs", g, root, chips=chips)
    rec = obs.TimelineRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        r = _run("bfs", g, root, chips=chips, observer=rec)
    finally:
        jax.profiler.stop_trace()
    assert np.array_equal(base.values, r.values)
    assert base.run.counters.as_dict() == r.run.counters.as_dict()
    assert base.run.trace.to_dict() == r.run.trace.to_dict()
    spans = _engine_spans(str(tmp_path))
    chunks = [s.index for s in rec.spans]
    assert len(chunks) > 1
    for name in ("engine.dispatch", "engine.fetch", "engine.account"):
        assert sorted(spans[name]) == chunks, name
    for name in ("engine.init_state", "engine.run_start", "engine.finish"):
        assert len(spans[name]) == 1, name


def _scopes(hlo_text: str) -> set:
    """Every scope name on every ``op_name`` path in HLO text, with
    transform wrappers taken off (``vmap(front)`` is ``front``)."""
    return {part.rsplit("(", 1)[-1].rstrip(")")
            for path in re.findall(r'op_name="([^"]*)"', hlo_text)
            for part in path.split("/")}


def test_chunk_program_carries_phase_scopes(g, root):
    import jax.numpy as jnp
    from repro.core.engine import DataLocalEngine, EngineConfig
    cfg = EngineConfig(grid=GRID, n_src=g.n_rows, n_dst=g.n_cols, oq_cap=8,
                       proxy=apps.table2_proxy(GRID, "bfs"))
    eng = DataLocalEngine(apps.BFS_SPEC, cfg, g.row_lo, g.row_hi,
                          g.col_idx, g.weights)
    state = eng.init_state(seed_idx=root, seed_val=0.0)
    false = jnp.zeros((), jnp.bool_)
    hlo = eng._chunk.lower(eng.graph, state, false, false, jnp.int32(64),
                           length=CHUNK).compile().as_text()
    assert set(PHASES) <= _scopes(hlo)


def test_mesh_chunk_program_carries_exchange_scope(tmp_path):
    """On a 4-device mesh the compiled chunk program (the one that ran,
    dumped by XLA) also carries the board exchange's scope."""
    out = run_devices(f"""
os.environ["XLA_FLAGS"] += " --xla_dump_to={tmp_path}"
import numpy as np
from repro.core.tilegrid import square_grid
from repro.graph import apps, rmat_edges
g = rmat_edges(7, edge_factor=8, seed=1)
grid = square_grid(16)
r = apps.bfs(g, int(np.argmax(g.out_degree())), grid, chips=4,
             backend="shard_map", proxy=apps.table2_proxy(grid, "bfs"),
             oq_cap=16, run_chunk=8)
print("supersteps", r.run.supersteps)
""", n=4)
    assert "supersteps" in out
    scoped = [_scopes(open(p).read()) for p in glob.glob(
        str(tmp_path / "*after_optimizations.txt"))]
    chunk = [s for s in scoped if "exchange" in s]
    assert chunk, "no compiled program carries the exchange scope"
    assert all(set(PHASES) <= s for s in chunk)


# ------------------------------------------------------ trace-event export
@pytest.fixture(scope="module")
def bfs4_rec(g, root):
    rec = obs.TimelineRecorder()
    r = _run("bfs", g, root, chips=4, telemetry=True, observer=rec)
    return rec, r


def test_trace_event_schema(bfs4_rec, tmp_path):
    rec, _ = bfs4_rec
    path = str(tmp_path / "trace.json")
    obs.write_trace(rec, path)
    with open(path) as f:
        d = json.load(f)
    assert set(d) == {"traceEvents", "displayTimeUnit", "otherData"}
    assert d["otherData"]["n_chips"] == 4
    evs = d["traceEvents"]
    assert evs and all(e["ph"] in ("M", "X", "C") for e in evs)
    for e in evs:
        assert {"ph", "pid", "tid", "name"} <= set(e)
        if e["ph"] == "X":
            assert e["ts"] >= 0.0 and e["dur"] >= 0.0


def test_trace_has_span_per_chunk_per_track(bfs4_rec):
    rec, _ = bfs4_rec
    evs = obs.to_trace_events(rec)
    host_x = [e for e in evs
              if e["ph"] == "X" and e["pid"] == obs_export.PID_HOST]
    # one complete span per chunk on each of dispatch/fetch/account
    assert len(host_x) == 3 * len(rec.spans)
    for s in rec.spans:
        label = f"chunk {s.index} [{s.step_lo}:{s.step_hi})"
        assert sum(e["name"] == label for e in host_x) == 3
    sim_x = [e for e in evs
             if e["ph"] == "X" and e["pid"] == obs_export.PID_SIM]
    assert sim_x, "no simulated BSP spans"
    counters = [e for e in evs if e["ph"] == "C"]
    pids = {e["pid"] for e in counters}
    assert pids == {obs_export.PID_CHIP0 + c for c in range(4)}


# --------------------------------------------- compaction telemetry track
def test_compaction_track_schema_and_metrics(g, root, bfs4_rec):
    """Compacted runs emit the active-set counter track (one
    active_fraction + bucket_cap sample per superstep, on the sim
    process) plus the engine.active_fraction gauge and per-capacity
    bucket-occupancy counters — all riding the existing chunk stat
    fetch.  Dense runs emit none of it."""
    reg = default_registry()
    before = dict(reg.snapshot()["counters"])
    rec = obs.TimelineRecorder()
    r = _run("bfs", g, root, telemetry=True, observer=rec, compaction=2)
    evs = obs.to_trace_events(rec)
    comp = [e for e in evs if e["ph"] == "C"
            and e["pid"] == obs_export.PID_SIM
            and e["tid"] == obs_export._TID_COMPACTION]
    fracs = [e for e in comp if e["name"] == "active_fraction"]
    caps = [e for e in comp if e["name"] == "bucket_cap"]
    assert len(fracs) == r.run.supersteps
    assert len(caps) == r.run.supersteps
    assert all(0.0 <= e["args"]["active_fraction"] <= 1.0 for e in fracs)
    from repro.core.engine import capacity_ladder
    ladder = set(map(float, capacity_ladder(GRID.ny * GRID.nx, 2)))
    assert {e["args"]["bucket_cap"] for e in caps} <= ladder
    for e in comp:                       # schema: counter-track events
        assert {"ph", "pid", "tid", "name", "ts", "args"} <= set(e)
        assert e["ts"] >= 0.0
    snap = reg.snapshot()
    assert 0.0 <= snap["gauges"]["engine.active_fraction"] <= 1.0
    occ = {k: v - before.get(k, 0.0)
           for k, v in snap["counters"].items()
           if k.startswith("engine.bucket_occupancy.")}
    occ = {k: v for k, v in occ.items() if v}
    assert occ, "no bucket-occupancy counters incremented"
    assert {float(k.rsplit(".", 1)[1]) for k in occ} <= ladder
    assert sum(occ.values()) == r.run.supersteps
    # dense run (module fixture): no compaction track at all
    dense_rec, _ = bfs4_rec
    dense = [e for e in obs.to_trace_events(dense_rec)
             if e.get("tid") == obs_export._TID_COMPACTION]
    assert dense == []


# ------------------------------------------------------- imbalance metrics
def _gini_oracle(x):
    """O(n²) mean-absolute-difference definition."""
    x = np.asarray(x, np.float64)
    n, s = x.size, float(x.sum())
    if n == 0 or s <= 0:
        return 0.0
    return float(np.abs(x[:, None] - x[None, :]).sum() / (2.0 * n * s))


def test_gini_matches_oracle(rng):
    for n in (1, 2, 3, 7, 32):
        x = rng.random(n) * 10.0
        assert obs.gini(x) == pytest.approx(_gini_oracle(x), abs=1e-12)
    ints = rng.integers(0, 50, 16).astype(float)
    assert obs.gini(ints) == pytest.approx(_gini_oracle(ints), abs=1e-12)
    assert obs.gini(np.array([])) == 0.0
    assert obs.gini(np.zeros(5)) == 0.0
    assert obs.gini(np.full(9, 3.0)) == pytest.approx(0.0, abs=1e-12)
    # one worker holds everything: (n-1)/n
    assert obs.gini(np.array([0.0, 0.0, 0.0, 7.0])) == pytest.approx(0.75)


def test_summarize_hand_built():
    load = np.array([[1.0, 1.0, 1.0, 1.0],
                     [0.0, 0.0, 0.0, 8.0],
                     [0.0, 0.0, 0.0, 0.0]])
    s = obs_imbalance.summarize(load, top=2)
    assert s["supersteps"] == 3 and s["workers"] == 4
    # totals per worker: [1, 1, 1, 9]
    assert s["total_gini"] == pytest.approx(_gini_oracle([1, 1, 1, 9]))
    assert s["total_max_over_mean"] == pytest.approx(9.0 / 3.0)
    # idle step 2 excluded from per-step means
    assert s["mean_step_gini"] == pytest.approx((0.0 + 0.75) / 2.0)
    assert s["max_step_gini"] == pytest.approx(0.75)
    assert s["mean_step_max_over_mean"] == pytest.approx((1.0 + 4.0) / 2.0)
    assert [t["step"] for t in s["top_steps"]] == [1, 0]
    assert s["top_steps"][0]["load"] == pytest.approx(8.0)


def test_max_over_mean():
    assert obs.max_over_mean([2.0, 2.0]) == pytest.approx(1.0)
    assert obs.max_over_mean([0.0, 4.0]) == pytest.approx(2.0)
    assert obs.max_over_mean([]) == 0.0
    assert obs.max_over_mean([0.0, 0.0]) == 0.0


def test_cascade_efficacy_formula():
    assert obs.cascade_efficacy(50.0, 100.0) == pytest.approx(0.5)
    assert obs.cascade_efficacy(100.0, 100.0) == pytest.approx(0.0)
    assert obs.cascade_efficacy(150.0, 100.0) == pytest.approx(-0.5)
    assert obs.cascade_efficacy(10.0, 0.0) == 0.0


def test_cascade_improves_measured_balance(g, root):
    """The paper's load-balance claim, measured: on the 8x8-tile 4-chip
    partition, BFS with a 2-level cascade tree has lower whole-run Gini
    than the same proxy without cascading, and positive cascade efficacy
    vs the no-proxy baseline."""
    grid = square_grid(64)
    base = apps.bfs(g, root, grid, oq_cap=16, run_chunk=CHUNK, chips=4)
    recs = {}
    for levels in (0, 2):
        rec = obs.TimelineRecorder()
        px = apps.table2_proxy(grid, "bfs", cascade_levels=levels,
                               selective=False)
        apps.bfs(g, root, grid, proxy=px, oq_cap=16, run_chunk=CHUNK,
                 chips=4, telemetry=True, observer=rec)
        recs[levels] = rec
    rep_on = obs.imbalance_report(recs[2], base.run.counters)
    rep_off = obs.imbalance_report(recs[0], base.run.counters)
    assert rep_on["total_gini"] <= rep_off["total_gini"]
    assert rep_on["cascade_efficacy"] > 0.0
    assert rep_on["owner_msgs"] < rep_on["baseline_owner_msgs"]


# ----------------------------------------------------------- run report
def test_run_report_and_markdown(bfs4_rec, tmp_path):
    rec, r = bfs4_rec
    rep = obs_report.run_report(rec, teps_edges=r.teps_edges)
    assert rep["app"] == "bfs" and rep["n_chips"] == 4
    assert rep["supersteps"] == r.run.supersteps
    assert rep["sim_time_s"] == pytest.approx(float(r.run.time_s))
    assert rep["gteps"] == pytest.approx(r.gteps)
    assert rep["counters"] == r.run.counters.as_dict()
    assert sum(rep["superstep_histogram"]["counts"]) == r.run.supersteps
    assert rep["sanitizer"]["status"] == "off"
    assert rep["imbalance"]["supersteps"] == r.run.supersteps
    paths = obs.write_report(rep, str(tmp_path / "rep"))
    with open(paths["json"]) as f:
        assert json.load(f)["app"] == "bfs"
    md = open(paths["markdown"]).read()
    assert md.startswith("# Run report: bfs")
    assert "Load imbalance" in md


# ------------------------------------------------------- metrics registry
def test_metrics_registry_basics():
    reg = MetricsRegistry()
    c = reg.counter("a.b")
    c.inc()
    c.inc(2.0)
    assert c.value == 3.0
    assert reg.counter("a.b") is c
    reg.gauge("g").set(7)
    snap = reg.snapshot()
    assert snap["counters"]["a.b"] == 3.0
    assert snap["gauges"]["g"] == 7.0
    assert json.dumps(snap)          # JSON-serializable
    reg.reset()
    assert reg.snapshot() == dict(counters={}, gauges={})


def test_progress_reporter_emits_metrics(g, root, capsys):
    reg = default_registry()
    before = reg.snapshot()["counters"].get("progress.bfs.reports", 0.0)
    from repro.core.engine import DataLocalEngine, EngineConfig
    cfg = EngineConfig(grid=GRID, n_src=g.n_rows, n_dst=g.n_cols, oq_cap=8)
    eng = DataLocalEngine(apps.BFS_SPEC, cfg, g.row_lo, g.row_hi,
                          g.col_idx, g.weights)
    eng.run(eng.init_state(seed_idx=root, seed_val=0.0),
            progress_every=5, chunk=4)
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if "step " in ln]
    assert lines
    snap = reg.snapshot()
    assert snap["counters"]["progress.bfs.reports"] - before == len(lines)
    assert snap["gauges"]["progress.bfs.steps"] > 0


def test_sanitize_progress_line_reports_violations(g, root, capsys):
    from repro.core.engine import DataLocalEngine, EngineConfig
    cfg = EngineConfig(grid=GRID, n_src=g.n_rows, n_dst=g.n_cols,
                       oq_cap=8, sanitize=True)
    eng = DataLocalEngine(apps.BFS_SPEC, cfg, g.row_lo, g.row_hi,
                          g.col_idx, g.weights)
    eng.run(eng.init_state(seed_idx=root, seed_val=0.0),
            progress_every=5, chunk=4)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if "step " in ln]
    assert lines
    assert all("sanity_violations=0" in ln for ln in lines)
