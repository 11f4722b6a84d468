"""Benchmark harness driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows and writes
``BENCH_manifest.json`` (benchmark name → status / wall time / output
file, plus the git SHA) so the bench trajectory is machine-readable
across PRs.  ``--trace out.json`` instead exports a BFS 4-chip telemetry
run as Chrome trace-event JSON (load it in chrome://tracing or
ui.perfetto.dev) plus the markdown+JSON run report next to it.
REPRO_BENCH_SCALE=k bumps dataset/grid sizes for longer runs.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

MODULES = [
    ("engine_throughput", "Run-loop throughput: chunked vs legacy loop"),
    ("fig6_network", "Fig. 6  network link-width options"),
    ("fig7_queues", "Fig. 7  IQ:OQ ratio (Goldilocks)"),
    ("fig8_proxy", "Fig. 8  proxies vs Dalorex"),
    ("fig9_packaging", "Fig. 9  packaging: thr/$ & eff/$"),
    ("fig10_energy", "Fig. 10 energy breakdown"),
    ("fig11_scaling", "Fig. 11 strong scaling"),
    ("product_search", "Package-time product search (measure-once/price-many)"),
    ("multichip_scaling", "Multi-chip weak/strong scaling (distributed)"),
    ("graph500_compare", "Graph500 BFS accounting + measured multi-chip"),
    ("kernels_bench", "Pallas kernel microbench"),
    ("roofline", "Roofline terms from dry-run artifacts"),
]

MANIFEST_OUT = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_manifest.json")


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(__file__), timeout=10,
        ).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def export_trace(trace_path: str, report_stem: str | None = None) -> None:
    """The ``--trace`` CLI path: run BFS 4-chip chunked with telemetry on
    the RMAT test graph, export the Chrome trace-event JSON and the run
    report (same artifacts the tier1 CI smoke step uploads)."""
    import numpy as np

    from repro import obs
    from repro.core.tilegrid import square_grid
    from repro.graph import apps, rmat_edges

    grid = square_grid(64)
    g = rmat_edges(8, edge_factor=8, seed=1)
    root = int(np.argmax(g.out_degree()))
    rec = obs.TimelineRecorder()
    baseline = apps.bfs(g, root, grid, oq_cap=16, run_chunk=8, chips=4)
    r = apps.bfs(g, root, grid,
                 proxy=apps.table2_proxy(grid, "bfs", cascade_levels=2,
                                         selective=False),
                 oq_cap=16, run_chunk=8, chips=4, telemetry=True,
                 observer=rec)
    out_dir = os.path.dirname(os.path.abspath(trace_path))
    os.makedirs(out_dir, exist_ok=True)
    obs.write_trace(rec, trace_path)
    stem = report_stem or os.path.splitext(trace_path)[0] + "_report"
    paths = obs.write_report(
        obs.run_report(rec, teps_edges=r.teps_edges,
                       baseline_counters=baseline.run.counters), stem)
    print(f"# trace: {trace_path}")
    print(f"# report: {paths['json']} {paths['markdown']}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", metavar="OUT_JSON",
                    help="export a BFS 4-chip telemetry trace "
                         "(Chrome trace-event JSON) + run report and exit")
    ap.add_argument("--report-stem", default=None,
                    help="with --trace: write the run report at this stem "
                         "(default: alongside the trace)")
    ap.add_argument("--manifest", default=MANIFEST_OUT,
                    help="where to write BENCH_manifest.json")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        export_trace(args.trace, args.report_stem)
        return

    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    manifest = dict(git_sha=_git_sha(), benchmarks={})
    for mod_name, desc in MODULES:
        print(f"# === {mod_name}: {desc} ===", flush=True)
        m0 = time.time()
        entry = dict(description=desc, status="ok")
        try:
            mod = __import__(mod_name)
            mod.run(small=True)
            out = getattr(mod, "DEFAULT_OUT", None)
            if out:
                entry["output"] = os.path.relpath(
                    os.path.abspath(out),
                    os.path.dirname(os.path.abspath(args.manifest)))
        except Exception as e:
            failures += 1
            entry["status"] = f"failed: {type(e).__name__}: {e}"
            print(f"# FAILED {mod_name}: {type(e).__name__}: {e}",
                  flush=True)
            traceback.print_exc()
        entry["wall_s"] = round(time.time() - m0, 3)
        manifest["benchmarks"][mod_name] = entry
    manifest["wall_s"] = round(time.time() - t0, 3)
    with open(args.manifest, "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# manifest: {args.manifest}")
    print(f"# total {time.time()-t0:.1f}s, failures={failures}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
