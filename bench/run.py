"""The benchmark: one run of one cell on the chips this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout that holds the program under ``src/``.
It needs TPUs, as many as the cell's ``chips``, and has no CPU path:
without them it exits nonzero and prints no result.  With ``--trace 0``
the result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the first query and
from the harness's spans.  Standard error ends with each number that
decides ``correct`` beside its limit; standard output ends with the
result, one JSON object.

JAX's persistent compilation cache is the program's
(``repro.runtime.compile_cache``): ``$JAX_COMPILATION_CACHE_DIR`` where
that is set, else ``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def tpu_devices(chips: int, peaks: dict):
    """The cell's chips: the first ``chips`` TPUs, whose kind the peaks
    table must know."""
    import jax
    tpus = [d for d in jax.devices() if d.platform == "tpu"]
    if len(tpus) < chips:
        raise SystemExit(f"bench: the cell needs {chips} TPU(s), found "
                         f"{len(tpus)} ({jax.devices()[0].platform})")
    if tpus[0].device_kind not in peaks:
        raise SystemExit(f"bench: no peaks for {tpus[0].device_kind!r} in "
                         f"bench/peaks.json")
    return tpus[:chips]


def main(argv=None) -> int:
    args = _args(argv)
    try:
        sys.path.insert(0, BENCH_DIR)
        sys.path.insert(0, os.path.join(ROOT, "src"))
        import harness
        from loader import Benchmark

        bench = Benchmark(ROOT, BENCH_DIR)
        cell = bench.cell(args.workload)
        with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
            peaks = json.load(f)["devices"]
        import jax
        from repro.runtime.compile_cache import enable_compile_cache
        devices = tpu_devices(cell["chips"], peaks)
        cache = enable_compile_cache()
        # JAX caches only programs that took a second or more to compile;
        # the smaller ones would compile again in every run's set-up
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        print(f"compile_cache {cache}", file=sys.stderr)
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace), devices,
                                  T_START)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    except Exception:
        traceback.print_exc()
        return 1
    hbm = peaks[devices[0].device_kind]["hbm_bytes"]
    if result["device"]["memory_peak_bytes"]:
        print(f"memory_share {result['device']['memory_peak_bytes'] / hbm}",
              file=sys.stderr)
    checks = result["checks"]
    for k, v in checks.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
