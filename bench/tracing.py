"""Reduction of a profiler trace to device busy, idle and collective time.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists: per device plane, the events of its ``XLA Ops`` line (one
per executed HLO operation, named by the instruction's name, such as
``fusion.12``; a ``while`` encloses the operations of its body); on the
host, the harness's own ``bench.*`` annotations.  Both are on the
profiler's one clock.  ``summarize`` reduces them over the traced
window, the span of the ``bench.query`` annotation:

* busy: the union of the operation intervals of a device;
* idle: the rest of the window, cut into gaps, each named by the
  harness span the host was in at the gap's middle;
* collective: the summed durations of the collective operations
  (all-gather, all-reduce, all-to-all, reduce-scatter, collective
  permute and broadcast, with their async start/done halves);
* the operations that took most time, counting only operations that
  enclose no other (a loop's time is its body's).

Kept as code with the benchmark so that every PR reduces a trace the
same way; ``bench/tests/test_bench_tracing.py`` checks it on a recorded trace.
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
QUERY_SPAN = "bench.query"
COLLECTIVE = re.compile(r"(all-gather|all-reduce|all-to-all|reduce-scatter|"
                        r"collective-permute|collective-broadcast)")


def op_name(hlo: str) -> str:
    """``fusion.12`` from ``%fusion.12 = f32[...] fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    return paths[0]


def extract(path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, end_ns], ...]},
    "host": [[name, start_ns, end_ns], ...]}`` from one xplane file."""
    from jax.profiler import ProfileData
    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.start_ns + e.duration_ns]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def _union(intervals):
    """Sorted, merged ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _leaves(events):
    """The events that enclose no other event."""
    ordered = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    return [ev for ev, nxt in zip(ordered, ordered[1:] + [None])
            if nxt is None or nxt[1] >= ev[2] or nxt[2] > ev[2]]


def _span_at(host, t):
    """The innermost harness span (other than the query) holding ``t``."""
    best = None
    for name, s, e in host:
        if name != QUERY_SPAN and s <= t < e:
            if best is None or e - s < best[2] - best[1]:
                best = (name, s, e)
    return best[0][len("bench."):] if best else "query"


def summarize(events: dict, top: int = 10) -> dict:
    """Busy, idle and collective seconds of each device over the traced
    window, with the operations that took most time and the longest
    idle gaps."""
    spans = [h for h in events["host"] if h[0] == QUERY_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {QUERY_SPAN} span, found "
                           f"{len(spans)}")
    w0, w1 = spans[0][1], spans[0][2]
    devices = sorted(events["devices"])
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    busy, coll, ops, gaps = [], [], {}, []
    for dev in devices:
        clipped = [(n, max(s, w0), min(e, w1))
                   for n, s, e in events["devices"][dev] if e > w0 and s < w1]
        merged = _union([[s, e] for _, s, e in clipped])
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        coll.append(sum(e - s for n, s, e in clipped
                        if COLLECTIVE.match(n)) * 1e-9)
        for n, s, e in _leaves(clipped):
            ops[n] = ops.get(n, 0.0) + (e - s) * 1e-9 / len(devices)
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_span_at(events["host"], (s + e) / 2),
                             (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    window_s = (w1 - w0) * 1e-9
    return dict(
        devices=devices,
        window_s=window_s,
        busy_s=busy,
        collective_s=coll,
        device_ops=sorted(([n, t] for n, t in ops.items()),
                          key=lambda x: -x[1])[:top],
        idle_gaps=[[n, t] for n, t in gaps[:top]],
    )
