"""The program-trace reduction gives known phase, chunk-idle and gap
numbers."""
import gzip
import json
import os
import statistics
import struct

import pytest

import bench_testlib  # noqa: F401  (import paths)
import phases
import tracing

MS = 1_000_000                    # ns


@pytest.mark.parametrize("path, phase", [
    ("jit(_chunk_impl)/while/body/closed_call/front/gather", "front"),
    ("jit(fn)/shard_map/while/body/closed_call/cond/branch_1_fun/"
     "vmap(proxy)/delivery/charge/jit(floor_divide)/rem", "charge"),
    ("jit(fn)/shard_map/while/body/vmap(proxy)/lt", "proxy"),
    ("jit(_chunk_impl)/while/body/proxy/delivery/scatter-add", "delivery"),
    ("jit(fn)/shard_map/while/body/closed_call/exchange/psum", "exchange"),
    ("jit(_chunk_impl)/while/body/closed_call/add", "other"),
    ("jit(frontier)/while/body/add", "other"),
    ("", "other"),
])
def test_phase_is_the_innermost_scope(path, phase):
    assert phases.phase_of(path) == phase


def _field(num: int, value) -> bytes:
    """One protobuf field: a varint for an int, a float as fixed64, a
    length-delimited string or message otherwise."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
            n >>= 7
            if not n:
                return bytes(out)
    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, float):
        return varint(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def _msg(*fields) -> bytes:
    return b"".join(_field(n, v) for n, v in fields)


def test_scope_paths_read_the_event_metadata(tmp_path):
    """The scope path is the ``tf_op`` stat of an event's metadata, as a
    string or as a reference to a stat metadata's name."""
    def entry(key, value):                       # one map<int64, msg> item
        return _msg((1, key), (2, value))
    stat_meta = [entry(7, _msg((1, 7), (2, "tf_op"))),
                 entry(8, _msg((1, 8), (2, "flops"))),
                 entry(9, _msg((1, 9), (2, "jit(f)/proxy/sort:sort")))]
    event_meta = [
        entry(1, _msg((1, 1), (2, "%fusion.1 = f32[8] fusion(...)"),
                      (5, _msg((1, 8), (2, 2.5))),
                      (5, _msg((1, 7), (5, "jit(f)/front/gather:gather"))))),
        entry(2, _msg((1, 2), (2, "%sort.2 = f32[8] sort(...)"),
                      (5, _msg((1, 7), (7, 9))))),
        entry(3, _msg((1, 3), (2, "%copy.3 = f32[8] copy(...)")))]
    device = _msg((1, 5), (2, "/device:TPU:0"),
                  *[(4, e) for e in event_meta], *[(5, e) for e in stat_meta])
    host = _msg((2, "/host:CPU"), (4, event_meta[0]), (5, stat_meta[0]))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, host), (1, device)))
    assert phases.scope_paths(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion(...)": "jit(f)/front/gather",
        "%sort.2 = f32[8] sort(...)": "jit(f)/proxy/sort"}}


def hand_built():
    # window 0..100 ms.  The host is in engine.run_start 5..8 and runs
    # three chunks; device A runs a loop (10..29) around three phased
    # operations (10..28), then one operation a chunk; device B is busy
    # all through.  A's gaps: 0..10 (middle 5: run_start), 29..33 (31:
    # chunk 0's account), 40..71 (55.5: chunk 1's fetch, the long one),
    # 87..90 (88.5: chunk 2's account), 92..100 (96: no engine span;
    # the harness's fetch).
    chunk = [("engine.dispatch", 8, 10, 0), ("engine.fetch", 10, 30, 0),
             ("engine.account", 30, 32, 0), ("engine.dispatch", 32, 34, 1),
             ("engine.fetch", 34, 68, 1), ("engine.account", 68, 70, 1),
             ("engine.dispatch", 70, 72, 2), ("engine.fetch", 72, 88, 2),
             ("engine.account", 88, 91, 2)]
    return {
        "devices": {
            "/device:TPU:0": [["while.1", 10 * MS, 29 * MS, "other"],
                              ["fusion.1", 10 * MS, 20 * MS, "front"],
                              ["fusion.2", 20 * MS, 24 * MS, "charge"],
                              ["fusion.3", 24 * MS, 28 * MS, "proxy"],
                              ["fusion.4", 33 * MS, 40 * MS, "delivery"],
                              ["fusion.5", 71 * MS, 87 * MS, "exchange"],
                              ["copy.6", 90 * MS, 92 * MS, "other"]],
            "/device:TPU:1": [["fusion.1", 0, 100 * MS, "front"]],
        },
        "host": [["bench.query", 0, 100 * MS], ["bench.prep", 0, 5 * MS],
                 ["bench.run", 5 * MS, 95 * MS],
                 ["bench.fetch", 95 * MS, 100 * MS]],
        "program": [["engine.init_state", 1 * MS, 4 * MS, None],
                    ["engine.run_start", 5 * MS, 8 * MS, None]]
        + [[n, s * MS, e * MS, c] for n, s, e, c in chunk]
        + [["engine.finish", 91 * MS, 94 * MS, None]],
    }


def without_phases(events):
    """``tracing``'s form of the same events."""
    return dict(events, devices={d: [op[:3] for op in ops]
                                 for d, ops in events["devices"].items()})


def test_hand_built_phases_and_gaps():
    s = phases.summarize(hand_built())
    assert s["devices"] == ["/device:TPU:0", "/device:TPU:1"]
    # self time: the loop's 19 ms less its body's 18 ms is its own 1 ms,
    # in its phase (other) beside copy.6's 2 ms; the phases of a device
    # sum to its busy time
    want = dict(front=[0.010, 0.100], proxy=[0.004, 0.0],
                delivery=[0.007, 0.0], charge=[0.004, 0.0],
                exchange=[0.016, 0.0], other=[0.003, 0.0])
    assert s["phase_s"] == {k: pytest.approx(v) for k, v in want.items()}
    busy = tracing.summarize(without_phases(hand_built()))["busy_s"]
    assert [sum(v[i] for v in s["phase_s"].values()) for i in (0, 1)] \
        == pytest.approx(busy)
    # per chunk, the gaps in its spans, averaged over the two devices
    assert s["chunk_idle_s"] == [[0, pytest.approx(0.002)],
                                 [1, pytest.approx(0.0155)],
                                 [2, pytest.approx(0.0015)]]
    # the median shrugs off the one long gap; a mean would not
    idle = [t for _, t in s["chunk_idle_s"]]
    assert statistics.median(idle) == pytest.approx(0.002)
    assert s["idle_gaps"] == [["engine.fetch", pytest.approx(0.031)],
                              ["engine.run_start", pytest.approx(0.010)],
                              ["fetch", pytest.approx(0.008)],
                              ["engine.account", pytest.approx(0.004)],
                              ["engine.account", pytest.approx(0.003)]]


def test_window_bounds_the_chunks():
    """Spans and operations outside ``bench.query`` count for nothing."""
    ev = hand_built()
    ev["program"].append(["engine.fetch", 120 * MS, 130 * MS, 9])
    ev["devices"]["/device:TPU:0"].append(["fusion.9", 120 * MS, 125 * MS,
                                           "front"])
    s = phases.summarize(ev)
    assert [c for c, _ in s["chunk_idle_s"]] == [0, 1, 2]
    assert s["phase_s"]["front"] == pytest.approx([0.010, 0.100])


def test_recorded_v5e_engine_trace():
    """A trace recorded on one TPU v5e: the first three 16-superstep
    chunks of a query of ``bfs.rmat18.1pkg.g500roots`` inside the
    harness's spans, reduced by ``extract`` (gzipped)."""
    path = os.path.join(bench_testlib.TESTS, "data",
                        "trace_v5e_engine.json.gz")
    with gzip.open(path, "rt") as f:
        events = json.load(f)
    spans = [p[0] for p in events["program"]]
    assert spans == (["engine.init_state", "engine.run_start"]
                     + ["engine.dispatch", "engine.fetch",
                        "engine.account"] * 3 + ["engine.finish"])
    s = phases.summarize(events)
    # bench.query: 46,062,380 .. 2,854,963,016 ns; the phases' self
    # times sum to tracing's busy time, 2,780,129,536 ns
    busy = tracing.summarize(without_phases(events))["busy_s"]
    assert busy == pytest.approx([2.780129536])
    assert sum(v[0] for v in s["phase_s"].values()) == pytest.approx(
        busy[0])
    want = dict(front=1.614878352, proxy=0.690563516, delivery=0.334197675,
                charge=0.013193245, exchange=0.0, other=0.127296748)
    assert {k: v[0] for k, v in s["phase_s"].items()} == {
        k: pytest.approx(v) for k, v in want.items()}
    # each boundary: the fetch returns ~3 ms after the chunk's last
    # operation, then account (0.4 ms) and the next dispatch; the gap's
    # middle lies in the fetch
    assert s["chunk_idle_s"] == [[0, pytest.approx(0.003904883)],
                                 [1, pytest.approx(0.004055527)],
                                 [2, pytest.approx(0.003996581)]]
    names = [n for n, _ in s["idle_gaps"]]
    assert names[:3] == ["engine.init_state", "engine.fetch",
                         "engine.fetch"]
    assert set(names) == {"engine.init_state", "engine.fetch"}
