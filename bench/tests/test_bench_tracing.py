"""The trace reduction gives known busy, idle and collective numbers."""
import json
import os

import pytest

import bench_testlib as tl
import tracing

MS = 1_000_000                    # ns


def test_hand_worked_events():
    # window 0..100 ms; device A runs 10..30 and 20..40 (overlapping:
    # busy 30 ms) and an all-reduce 60..70; device B runs 0..50 plus an
    # op outside the window.  The host is in "run" 5..80, "fetch" 80..95.
    events = {
        "devices": {
            "/device:TPU:0": [["fusion.1", 10 * MS, 30 * MS],
                              ["fusion.2", 20 * MS, 40 * MS],
                              ["all-reduce.3", 60 * MS, 70 * MS]],
            "/device:TPU:1": [["fusion.1", 0, 50 * MS],
                              ["fusion.1", 120 * MS, 130 * MS]],
        },
        "host": [["bench.query", 0, 100 * MS], ["bench.prep", 0, 5 * MS],
                 ["bench.run", 5 * MS, 80 * MS],
                 ["bench.fetch", 80 * MS, 95 * MS]],
    }
    s = tracing.summarize(events)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx([0.04, 0.05])
    assert s["collective_s"] == pytest.approx([0.01, 0.0])
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(0.035)]
    gaps = dict((round(t, 6), n) for n, t in s["idle_gaps"])
    # device A: 0..10 (prep/run boundary at 5: middle 5 -> run),
    # 40..60 run, 70..100 (middle 85: fetch); device B: 50..100 (75: run)
    assert gaps == {0.05: "run", 0.03: "fetch", 0.02: "run", 0.01: "run"}


def test_recorded_v5e_trace():
    """A trace recorded on one TPU v5e (a jitted loop of five 65,536-entry
    sorts inside the harness's spans), reduced from ``extract``'s form.
    The ``while`` encloses its body: busy counts it once, the operation
    list only its body."""
    path = os.path.join(tl.TESTS, "data", "trace_v5e_1chip.json")
    with open(path) as f:
        s = tracing.summarize(json.load(f))
    assert s["devices"] == ["/device:TPU:0"]
    # bench.query: 44,455,681 .. 102,811,221 ns
    assert s["window_s"] == pytest.approx(0.05835554)
    # union: 1468 + 5 + 866 + 297231 (the while) + 1 + 572 ns
    assert s["busy_s"] == pytest.approx([300143e-9])
    assert s["collective_s"] == [0.0]
    # five sorts: 59503 + 59206 + 59203 + 59399 + 59448 ns
    assert s["device_ops"][0] == ["sort.13", pytest.approx(296759e-9)]
    assert "while.2" not in dict(s["device_ops"])
    # before the first operation the host was preparing; after the last
    # it was inside run()
    assert s["idle_gaps"][:2] == [["prep", pytest.approx(52519663e-9)],
                                  ["run", pytest.approx(5026818e-9)]]
