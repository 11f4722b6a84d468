"""The harness finds configurations, cells, traffic and metrics by name,
so adding one adds files and edits none."""
import json
import os
import shutil

import bench_testlib as tl
from loader import Benchmark

EXECUTION_KNOBS = ("backend", "compaction", "run_chunk", "double_buffer")


def test_configs_set_no_execution_knob():
    bench = Benchmark()
    for entry in bench.spec["configs"]:
        cfg = bench.config(entry["name"])
        assert cfg["name"] == entry["name"]
        assert not [k for k in EXECUTION_KNOBS if k in cfg]


def test_every_metric_has_a_reader():
    bench = Benchmark()
    for m in bench.spec["end_to_end"] + bench.spec["per_layer"]:
        assert callable(bench.reader(m["name"]).read)


def _added_benchmark(tmp_path):
    """A benchmark tree with one more configuration, traffic mix, cell
    and metric, each added as a file, the rest copied unchanged."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(tl.BENCH, bench_dir,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    spec = json.load(open(os.path.join(tl.ROOT, "BENCHMARK.json")))
    cfg = tl.tiny_config(Benchmark(), tl.CELL_1)
    cfg["name"] = "tiny-bfs"
    (bench_dir / "configs" / "tiny-bfs.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "two-keys.json").write_text(json.dumps(
        {"name": "two-keys", "search_keys": 2, "min_degree": 1,
         "in_flight": 1}))
    (bench_dir / "metrics" / "queries_answered.py").write_text(
        "def read(run):\n    return len(run.queries)\n")
    spec["configs"].append({"name": "tiny-bfs",
                            "file": "bench/configs/tiny-bfs.json"})
    spec["workloads"].append({"name": "tiny.two-keys", "config": "tiny-bfs",
                              "traffic": "two-keys", "chips": 1})
    spec["end_to_end"].append({"name": "queries_answered", "unit": "queries",
                               "workloads": ["tiny.two-keys"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return Benchmark(str(tmp_path), str(bench_dir))


def test_added_files_are_found_by_name(tmp_path):
    bench = _added_benchmark(tmp_path)
    assert bench.cell("tiny.two-keys")["config"] == "tiny-bfs"
    assert bench.config("tiny-bfs")["scale"] == 8
    assert bench.traffic("two-keys")["search_keys"] == 2
    assert "queries_answered" in [
        m["name"] for m in bench.metrics("tiny.two-keys", trace=False)]
    assert "queries_answered" not in [
        m["name"] for m in bench.metrics(tl.CELL_1, trace=False)]


def test_added_cell_runs_and_reports_added_metric(tmp_path):
    import io
    import time

    import jax

    import harness
    bench = _added_benchmark(tmp_path)
    result = harness.run_cell(bench, "tiny.two-keys", 3, 60.0, False,
                              jax.devices()[:1], time.perf_counter(),
                              log=io.StringIO())
    assert result["correct"] and result["attempted"] == 2
    assert result["metrics"]["queries_answered"]["value"] == 2
