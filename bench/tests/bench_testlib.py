"""Shared set-up of the benchmark's tests: import paths and a cell cut
to a size the CPU runs in seconds."""
import os
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

CELL_1 = "bfs.rmat18.1pkg.g500roots"
CELL_4 = "bfs.rmat18.4pkg.g500roots"


def tiny_config(bench, cell: str, scale: int = 8) -> dict:
    """The cell's configuration at ``scale`` on a 4x4-tile grid (2x2
    packages of 2x2 tiles where the configuration has four)."""
    cfg = dict(bench.config(bench.cell(cell)["config"]))
    pkg = 2 if cfg["packages"] == 4 else 4
    cfg.update(scale=scale, tiles_y=4, tiles_x=4, die_tiles_y=2,
               die_tiles_x=2, package_tiles_y=pkg, package_tiles_x=pkg)
    return cfg


def run_tiny(cell: str, seed: int = 5, seconds: float = 0.3, **kw) -> dict:
    """One run of ``cell`` at the tiny size on the CPU, past the look
    for chips."""
    import io
    import time

    import jax

    import harness
    from loader import Benchmark
    bench = Benchmark()
    return harness.run_cell(bench, cell, seed, seconds, False,
                            jax.devices()[:1], time.perf_counter(),
                            config=tiny_config(bench, cell),
                            log=io.StringIO(), **kw)
