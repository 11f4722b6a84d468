"""The control of ``correct``: the reference put in the program's place.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 5]

Drives the harness exactly as a run does (the cell's graph, keys,
closed loop and comparison at the cell's own size), with the engine
replaced by the algorithm's reference in one of two variants, and
prints each seed's numbers compared:

* ``edge_cap``: each vertex expands only its first ``oq_cap`` edges,
  which breaks the configuration's guarantee that every edge of a
  reached vertex is traversed.  This is the control; it must come out
  not correct.
* ``bfloat16``: levels computed in the precision below float32.  Levels
  this shallow are exact in it, so it reads 0 and is no control.

The benchmark's own runs never run this.  It insists on the cell's TPUs
like a run, though the reference itself runs on the host.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


class ReferenceEngine:
    """The engine's ``init_state``/``run`` surface over a reference."""

    def __init__(self, alg, graph, **variant):
        self.alg, self.graph, self.variant = alg, graph, variant
        self.cfg = types.SimpleNamespace(run_chunk=1)

    def init_state(self, seed_idx, seed_val):
        return {"root": int(seed_idx)}

    def run(self, state, max_supersteps=None, observer=None):
        values = self.alg.levels(self.graph, state["root"], **self.variant)
        return {"values": values}, types.SimpleNamespace(supersteps=0)


def variants(cfg: dict) -> dict:
    import ml_dtypes
    return {"edge_cap": dict(max_edges_per_vertex=cfg["oq_cap"]),
            "bfloat16": dict(dtype=ml_dtypes.bfloat16)}


def reference_builder(variant: dict):
    """A ``build`` for ``harness.run_cell`` that puts the reference, in
    ``variant``, where the engine would be."""
    def build(cfg, alg, graph, root, chips):
        eng = ReferenceEngine(alg, graph, **variant)
        return eng, eng.init_state(root, 0.0)
    return build


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH_DIR)
    import io

    import harness
    import run
    from loader import Benchmark
    bench = Benchmark(run.ROOT, BENCH_DIR)
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    devices = run.tpu_devices(bench.cell(args.workload)["chips"], peaks)
    cfg = bench.config(bench.cell(args.workload)["config"])
    for seed in [int(s) for s in args.seeds.split(",")]:
        for name, variant in variants(cfg).items():
            res = harness.run_cell(bench, args.workload, seed, args.seconds,
                                   False, devices[:1], time.perf_counter(),
                                   log=io.StringIO(),
                                   build=reference_builder(variant))
            print(json.dumps(dict(seed=seed, variant=name,
                                  correct=res["correct"],
                                  attempted=res["attempted"],
                                  checks=res["checks"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
