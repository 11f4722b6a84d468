"""The control of ``correct`` comes out not correct, and the precision
below float32 does not (levels this shallow are exact in bfloat16)."""
import io
import time

import jax

import bench_testlib as tl
import control
import harness
from loader import Benchmark


def _run_reference(cell, variant_name, seed):
    bench = Benchmark()
    cfg = tl.tiny_config(bench, cell, scale=10)
    variant = control.variants(cfg)[variant_name]
    return harness.run_cell(bench, cell, seed, 0.5, False,
                            jax.devices()[:1], time.perf_counter(),
                            config=cfg, log=io.StringIO(),
                            build=control.reference_builder(variant))


def test_edge_cap_control_is_not_correct():
    for seed in (1, 2, 3):
        res = _run_reference(tl.CELL_1, "edge_cap", seed)
        assert not res["correct"]
        assert res["checks"]["level_mismatches"]["value"] > 0


def test_bfloat16_reference_reads_zero():
    res = _run_reference(tl.CELL_1, "bfloat16", 1)
    assert res["correct"]
    assert res["checks"]["level_mismatches"]["value"] == 0
