"""Engine run-loop throughput: device-resident (chunked) vs legacy loop.

Measures wall-clock supersteps/sec and simulated-GTEPS-per-wall-second
for BFS/SSSP/PageRank at 1024 (and, with --full, 4096) tiles, comparing
the legacy per-superstep dispatch loop (``run(chunk=0)``: one jitted
step + one host sync per superstep — the seed engine's behavior) against
the scan-chunked device-resident loop (``run(chunk=K)``: K supersteps
per dispatch, one host sync per chunk).  Both loops produce bit-identical
``TrafficCounters`` and ``SuperstepTrace`` — asserted on every row — so
the comparison is pure wall-clock.

Rows sweep ``oq_cap``: small OQ budgets mean many cheap supersteps (the
dispatch/sync-bound regime the chunked loop exists for — the paper's
runs take hundreds of thousands of such steps); large budgets mean fewer,
compute-heavy steps where the loop overhead is already amortized.  On a
CPU-only container the XLA superstep itself executes synchronously, so
the measured speedup is bounded by the step's own execution time; on an
async-dispatch accelerator backend the per-step host round-trip the
chunked loop eliminates is the dominant term.  ``host_syncs`` records
the exactly-measured O(supersteps) -> O(supersteps/K) sync reduction.

A third *compaction* leg rides the sparse-regime rows: the same chunked
run through the engine's shape-bucketed active-set path
(``EngineConfig.compaction``), asserted bit-identical (values, counters,
trace, superstep count) to the dense chunked run and asserted to pay the
exact same measured host-sync count (bucket selection is on-device,
inside the scan).  ``speedup_compaction`` is the dense-chunked /
compacted wall ratio and ``mean_active_fraction`` records how sparse the
run actually was (from the ``active_tiles`` telemetry stat, fetched with
the chunk stats — no extra syncs).

A second axis sweeps *devices*: on the CPU backend each
``DEVICE_CONFIGS`` row re-executes this script in a CPU-pinned subprocess
with ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (N = 1/2/4
forced CPU devices — a rehearsal of the mesh); on an accelerator the row
runs in this process over the devices present (one process holds the
chip: a child could not open it).  Either way it runs the 4-chip
distributed engine on the resulting ExecMesh, once with the synchronous
boundary exchange and once
double-buffered (``EngineConfig.double_buffer``).  Counters, values and
the physical trace are asserted identical between the two modes (the
double-buffer flag itself is excluded — it is priced, not measured);
``db_sim_win`` records the simulated-time win the overlapped exchange
buys, and ``speedup`` here is the sync/db *wall* ratio (noisy on CPU —
the sim win is the deterministic signal).

Emits BENCH_engine.json (list of per-config rows) for the perf
trajectory; --smoke runs one tiny config, asserts counter/trace
equality, and still writes the JSON (CI uploads it as an artifact).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

from common import row, timed  # noqa: F401  (path bootstrap)

import numpy as np

from repro.core.engine import DataLocalEngine, EngineConfig
from repro.core.tilegrid import square_grid
from repro.graph import apps, rmat_edges

DEFAULT_OUT = os.path.join(os.path.dirname(__file__), "..",
                           "BENCH_engine.json")


def _mk_engine(app_name: str, g, grid, oq_cap: int, use_proxy: bool,
               compaction: int = 0):
    spec = {"bfs": apps.BFS_SPEC, "sssp": apps.SSSP_SPEC,
            "pagerank": apps.PAGERANK_SPEC}[app_name]
    proxy = apps.table2_proxy(grid, app_name) if use_proxy else None
    cfg = EngineConfig(grid=grid, n_src=g.n_rows, n_dst=g.n_cols,
                       oq_cap=oq_cap, proxy=proxy, compaction=compaction)
    return spec, DataLocalEngine(spec, cfg, g.row_lo, g.row_hi, g.col_idx,
                                 g.weights)


def _init(app_name: str, eng, g, root):
    if app_name == "pagerank":
        deg = np.maximum(g.out_degree(), 1).astype(np.float32)
        contrib = 0.85 / g.n_rows / deg
        state = eng.init_state()
        return eng.activate_all(state, contrib)
    return eng.init_state(seed_idx=root, seed_val=0.0)


def _run_mode(app_name, eng, g, root, chunk, repeats: int, observer=None):
    """Best-of-N wall clock of a full drained run (compile excluded:
    the first run warms the jit cache).  Returns (best_s, RunResult,
    final_state) — the state feeds the compaction bit-identity check."""
    eng.run(_init(app_name, eng, g, root), chunk=chunk)      # warm/compile
    best, result, final = float("inf"), None, None
    for _ in range(repeats):
        state = _init(app_name, eng, g, root)
        t0 = time.time()
        st, r = eng.run(state, chunk=chunk, observer=observer)
        best = min(best, time.time() - t0)
        result, final = r, st
    return best, result, final


def bench_config(app_name: str, tiles: int, scale: int, oq_cap: int,
                 chunk: int, use_proxy: bool = False,
                 repeats: int = 3, compaction: int = 0) -> dict:
    """One benchmark row: legacy (chunk=0) vs chunked loop on the same
    engine, with bit-identity of counters/trace asserted.  With
    ``compaction > 0`` a third leg runs the same chunked loop through
    the shape-bucketed active-set path and records its wall clock,
    measured host syncs (must match the dense chunked loop — bucket
    selection happens on device inside the scan) and the run's mean
    active-tile fraction (from the ``active_tiles`` telemetry stat via
    a TimelineRecorder — rides the chunk fetch, no extra syncs)."""
    from repro.obs.metrics import default_registry
    g = rmat_edges(scale, edge_factor=8, seed=1)
    grid = square_grid(tiles)
    root = int(np.argmax(g.out_degree()))
    _, eng = _mk_engine(app_name, g, grid, oq_cap, use_proxy)
    sync_ctr = default_registry().counter("engine.host_syncs")
    t_legacy, r_legacy, _ = _run_mode(app_name, eng, g, root, 0, repeats)
    s0 = sync_ctr.value
    t_chunk, r_chunk, st_chunk = _run_mode(app_name, eng, g, root, chunk,
                                           repeats)
    syncs_chunked = (sync_ctr.value - s0) / (repeats + 1)  # incl. warm run

    counters_equal = (r_legacy.counters.as_dict()
                      == r_chunk.counters.as_dict())
    trace_equal = r_legacy.trace.to_dict() == r_chunk.trace.to_dict()
    assert counters_equal, f"{app_name}: chunked counters diverged"
    assert trace_equal, f"{app_name}: chunked trace diverged"
    steps = r_chunk.supersteps
    teps = float(g.nnz)          # simulated edges traversed (upper bound)
    out = dict(
        app=app_name, tiles=tiles, scale=scale, oq_cap=oq_cap,
        proxy=use_proxy, chunk=chunk, compaction=compaction,
        supersteps=steps,
        wall_s_legacy=t_legacy, wall_s_chunked=t_chunk,
        steps_per_s_legacy=steps / t_legacy,
        steps_per_s_chunked=steps / t_chunk,
        speedup=t_legacy / t_chunk,
        host_syncs_legacy=steps,
        host_syncs_chunked=-(-steps // chunk),
        sim_time_s=r_chunk.time_s,
        sim_gteps_per_wall_s_legacy=teps / r_chunk.time_s / 1e9 / t_legacy,
        sim_gteps_per_wall_s_chunked=teps / r_chunk.time_s / 1e9 / t_chunk,
        counters_equal=counters_equal, trace_equal=trace_equal,
    )
    if compaction:
        from repro import obs
        _, ceng = _mk_engine(app_name, g, grid, oq_cap, use_proxy,
                             compaction)
        rec = obs.TimelineRecorder()
        s1 = sync_ctr.value
        t_comp, r_comp, st_comp = _run_mode(app_name, ceng, g, root, chunk,
                                            repeats, observer=rec)
        syncs_comp = (sync_ctr.value - s1) / (repeats + 1)
        act = rec.stat_matrix("active_tiles")
        compaction_equal = (
            r_comp.counters.as_dict() == r_chunk.counters.as_dict()
            and r_comp.trace.to_dict() == r_chunk.trace.to_dict()
            and r_comp.supersteps == r_chunk.supersteps
            and bool(np.array_equal(np.asarray(st_comp["values"]),
                                    np.asarray(st_chunk["values"]))))
        assert compaction_equal, f"{app_name}: compacted run diverged"
        assert syncs_comp == syncs_chunked, \
            f"{app_name}: compaction changed the host-sync count"
        out.update(
            wall_s_compacted=t_comp,
            steps_per_s_compacted=steps / t_comp,
            speedup_compaction=t_chunk / t_comp,
            host_syncs_compacted=int(syncs_comp),
            mean_active_fraction=float(np.mean(act)) / (grid.ny * grid.nx)
            if act.size else 1.0,
            compaction_equal=compaction_equal,
        )
    row(f"engine_throughput/{app_name}-{tiles}t-oq{oq_cap}"
        f"{'-proxy' if use_proxy else ''}"
        f"{f'-c{compaction}' if compaction else ''}",
        t_chunk * 1e6,
        f"speedup={out['speedup']:.2f}x "
        f"steps/s {out['steps_per_s_legacy']:.0f}->"
        f"{out['steps_per_s_chunked']:.0f} "
        f"syncs {steps}->{out['host_syncs_chunked']}"
        + (f" compaction {out['speedup_compaction']:.2f}x "
           f"act {out['mean_active_fraction']:.3f}" if compaction else ""))
    return out


def _device_row(app_name: str, tiles: int, scale: int, oq_cap: int,
                chunk: int, use_proxy: bool, devices: int,
                repeats: int = 2) -> dict:
    """One devices-axis row, executed *inside* the forced-device-count
    subprocess: 4-chip distributed run, sync vs double-buffered exchange
    on the same ExecMesh, with bit-identity of everything but the priced
    overlap asserted."""
    import jax
    g = rmat_edges(scale, edge_factor=8, seed=1)
    grid = square_grid(tiles)
    root = int(np.argmax(g.out_degree()))
    proxy = apps.table2_proxy(grid, app_name) if use_proxy else None
    res = {}
    for db in (False, True):
        eng, state, _seeds = apps.engine_and_state(
            app_name, g, grid, proxy=proxy, root=root,
            backend="shard_map", chips=4, oq_cap=oq_cap,
            double_buffer=db)
        eng.run(state, chunk=chunk)                      # warm/compile
        best, r, fin = float("inf"), None, None
        for _ in range(repeats):
            t0 = time.time()
            st, rr = eng.run(state, chunk=chunk)
            best = min(best, time.time() - t0)
            r, fin = rr, st
        res[db] = (best, r, fin, eng.mesh.ndev)
    (t_sync, r_sync, st_sync, ndev), (t_db, r_db, st_db, _) = \
        res[False], res[True]
    td_s, td_d = r_sync.trace.to_dict(), r_db.trace.to_dict()
    td_s.pop("double_buffer"), td_d.pop("double_buffer")
    counters_equal = r_sync.counters.as_dict() == r_db.counters.as_dict()
    values_equal = bool(np.array_equal(np.asarray(st_sync["values"]),
                                       np.asarray(st_db["values"])))
    assert counters_equal, f"{app_name}: db counters diverged"
    assert td_s == td_d, f"{app_name}: db physical trace diverged"
    assert values_equal, f"{app_name}: db values diverged"
    assert r_sync.supersteps == r_db.supersteps
    return dict(
        app=app_name, tiles=tiles, scale=scale, oq_cap=oq_cap,
        proxy=use_proxy, chunk=chunk, chips=4, devices=devices,
        host_devices=jax.device_count(), mesh_devices=ndev,
        supersteps=r_sync.supersteps,
        wall_s_sync=t_sync, wall_s_db=t_db,
        speedup=t_sync / t_db,
        sim_time_s=r_sync.time_s, sim_time_s_db=r_db.time_s,
        db_sim_win=1.0 - r_db.time_s / r_sync.time_s,
        counters_equal=counters_equal, trace_equal=True,
        values_equal=values_equal,
    )


def bench_devices(app_name: str, tiles: int, scale: int, oq_cap: int,
                  chunk: int, use_proxy: bool, devices: int,
                  repeats: int = 2) -> dict:
    """One devices-axis row.  On the CPU backend: spawn the
    forced-device-count worker (the count must be baked into XLA_FLAGS
    before jax imports, hence the CPU-pinned re-exec) and collect its
    row.  On an accelerator: run in-process over the devices present,
    and refuse any other count."""
    import jax
    spec = dict(app_name=app_name, tiles=tiles, scale=scale,
                oq_cap=oq_cap, chunk=chunk, use_proxy=use_proxy,
                devices=devices, repeats=repeats)
    if jax.default_backend() != "cpu":
        if devices != jax.device_count():
            raise RuntimeError(
                f"{devices} devices requested but {jax.device_count()} "
                f"{jax.default_backend()} devices are present; on an "
                f"accelerator the devices axis runs over the devices "
                f"present")
        out = _device_row(**spec)
        _device_csv(out)
        return out
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices}").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.abspath(os.path.join(here, "..", "src")),
                    env.get("PYTHONPATH")) if p)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--_worker",
         json.dumps(spec)],
        env=env, capture_output=True, text=True, timeout=1200)
    if proc.returncode != 0:
        raise RuntimeError(
            f"device worker ({devices} devices) failed:\n"
            f"{proc.stdout[-1000:]}\n{proc.stderr[-2000:]}")
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("ROW ")]
    out = json.loads(lines[-1][4:])
    _device_csv(out)
    return out


def _device_csv(out: dict) -> None:
    row(f"engine_throughput/{out['app']}-4chips-{out['devices']}dev"
        f"{'-proxy' if out['proxy'] else ''}",
        out["wall_s_db"] * 1e6,
        f"db sim win {out['db_sim_win'] * 100:.1f}% "
        f"wall sync/db {out['speedup']:.2f}x "
        f"mesh {out['mesh_devices']}dev")


# (app, oq_cap, chunk, use_proxy, compaction): the dispatch-bound
# small-OQ regimes the chunked loop targets plus one compute-heavy point
# per app for contrast.  The compaction level adds a third leg to the
# row — the shape-bucketed active-set path — on the sparse-regime
# configs (small OQ => long drained tails with few active tiles, the
# regime compaction exists for); the dense-regime rows keep it off, so
# the axis records both sides of the sparsity contrast.
CONFIGS_1024 = [
    ("bfs", 1, 128, False, 3),
    ("bfs", 8, 32, False, 2),
    ("bfs", 1, 128, True, 2),
    ("sssp", 1, 128, False, 2),
    ("sssp", 8, 32, True, 0),
    ("pagerank", 4, 64, True, 0),
]
CONFIGS_4096 = [
    ("bfs", 1, 128, False, 3),
    ("sssp", 4, 64, True, 0),
    ("pagerank", 4, 64, True, 0),
]
# (app, tiles, scale, oq_cap, chunk, use_proxy) x DEVICE_COUNTS forced
# CPU devices: the 4-chip mesh sweep (sync vs double-buffered exchange).
DEVICE_CONFIGS = [
    ("bfs", 256, 10, 8, 32, False),
    ("sssp", 256, 10, 8, 32, True),
]
DEVICE_COUNTS = (1, 2, 4)


def run(small: bool = True, out_path: str = DEFAULT_OUT,
        device_counts=DEVICE_COUNTS) -> list:
    import jax
    if jax.default_backend() != "cpu" and device_counts:
        device_counts = (jax.device_count(),)    # the devices present
    rows = []
    for app_name, oq, chunk, px, comp in CONFIGS_1024:
        rows.append(bench_config(app_name, 1024, 11, oq, chunk, px,
                                 compaction=comp))
    if not small:
        for app_name, oq, chunk, px, comp in CONFIGS_4096:
            rows.append(bench_config(app_name, 4096, 13, oq, chunk, px,
                                     compaction=comp))
    for app_name, tiles, scale, oq, chunk, px in DEVICE_CONFIGS:
        for ndev in device_counts:
            rows.append(bench_devices(app_name, tiles, scale, oq, chunk,
                                      px, ndev))
    _write(rows, out_path)
    return rows


def smoke(out_path: str = DEFAULT_OUT) -> None:
    """CI gate: tiny grid, asserts chunked == legacy counters/trace for a
    write-through and a write-back app, writes the JSON artifact."""
    rows = [bench_config("bfs", 64, 9, 4, 16, False, repeats=1,
                         compaction=2),
            bench_config("pagerank", 64, 9, 8, 16, True, repeats=1)]
    for r in rows:
        assert r["counters_equal"] and r["trace_equal"]
        assert r["host_syncs_chunked"] < r["host_syncs_legacy"]
        if r["compaction"]:
            assert r["compaction_equal"]
            assert r["host_syncs_compacted"] >= 0
    _write(rows, out_path)
    print(f"# smoke OK -> {out_path}")


def _write(rows: list, out_path: str) -> None:
    payload = dict(
        benchmark="engine_throughput",
        description="device-resident (scan-chunked) run loop vs legacy "
                    "per-superstep dispatch; bit-identical counters/trace",
        rows=rows,
        best_speedup=max((r["speedup"] for r in rows
                          if "devices" not in r), default=0.0),
        best_db_sim_win=max((r["db_sim_win"] for r in rows
                             if "db_sim_win" in r), default=0.0),
        best_speedup_compaction=max(
            (r["speedup_compaction"] for r in rows
             if "speedup_compaction" in r), default=0.0),
        note="CPU-only container: speedup bounded by the XLA superstep's "
             "own synchronous execution time; on async-dispatch "
             "accelerator backends the eliminated per-step host sync is "
             "the dominant term. host_syncs_* records the exact "
             "O(supersteps) -> O(supersteps/K) reduction.",
    )
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print(f"# wrote {out_path} (best speedup "
          f"{payload['best_speedup']:.2f}x)")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI config, asserts bit-identity")
    ap.add_argument("--full", action="store_true",
                    help="include the 4096-tile grids")
    ap.add_argument("--devices", default=",".join(map(str, DEVICE_COUNTS)),
                    help="comma-separated forced CPU device counts for "
                         "the 4-chip mesh sweep (empty string skips it)")
    ap.add_argument("--out", default=DEFAULT_OUT,
                    help="output JSON path")
    ap.add_argument("--_worker", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    enable_compile_cache()
    if args._worker is not None:
        print("ROW " + json.dumps(_device_row(**json.loads(args._worker))))
    elif args.smoke:
        smoke(args.out)
    else:
        counts = tuple(int(c) for c in args.devices.split(",") if c)
        run(small=not args.full, out_path=args.out, device_counts=counts)
