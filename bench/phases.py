"""Reduction of the program's own instrumentation in a profiler trace:
device time by superstep phase, idle time at chunk boundaries, and idle
gaps named by the engine's host spans.

The engine wraps each superstep phase in a ``jax.named_scope``
(``PHASES``); a device operation belongs to the innermost of those names
on its scope path, and to ``other`` under none.  Scope names are
matched with transform wrappers taken off (``vmap(front)`` is
``front``).  The run loops write ``engine.*`` host spans on the
profiler's clock (``repro.obs.timeline.HostSpan``); the per-chunk ones
carry the chunk's index as their ``chunk`` argument.

``extract`` reads one ``.xplane.pb`` into plain lists: ``tracing``'s
device operations and harness spans, each operation with its phase as a
fourth element, and the program's spans under ``program``.
``summarize`` reduces them over the traced window, the span of
``bench.query``, as ``tracing.summarize`` does:

* ``phase_s``: per phase, each device's seconds of operation self time:
  an operation's time less that of the operations it encloses, so a
  loop's body counts in the body's phases and only the loop's own
  overhead in the loop's, and the phases sum to the device's busy time;
* ``chunk_idle_s``: per chunk, the idle gaps whose middle lies in one of
  that chunk's ``engine.dispatch`` / ``fetch`` / ``account`` spans,
  summed per device and averaged over devices;
* ``idle_gaps``: the longest gaps, each named by the innermost
  ``engine.*`` span holding its middle, else by the harness span as
  ``tracing`` names it.

``bench/tests/test_bench_phases.py`` checks it on a recorded trace.
"""
from __future__ import annotations

import tracing

PHASES = ("front", "proxy", "delivery", "charge", "exchange")
OTHER = "other"
PROGRAM = "engine."
CHUNK_SPANS = ("engine.dispatch", "engine.fetch", "engine.account")
# the stat that holds a device operation's scope path, as
# ``<path>:<op type>``; the profiler keeps it on the event's metadata
SCOPE_STAT = "tf_op"


def phase_of(scope_path: str) -> str:
    """The innermost phase name on ``scope_path``, else ``other``."""
    for part in reversed(scope_path.split("/")):
        name = part.rsplit("(", 1)[-1].rstrip(")")
        if name in PHASES:
            return name
    return OTHER


def _varint(buf: bytes, i: int):
    x = shift = 0
    while True:
        c = buf[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(buf: bytes):
    """``(field number, value)`` of each field of one protobuf message:
    an int for varints, bytes for the rest."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = buf[i:i + n], i + n
        elif kind in (1, 5):
            n = 8 if kind == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def scope_paths(path: str) -> dict:
    """``{device plane: {event name: scope path}}`` from one xplane file.

    The path is the ``SCOPE_STAT`` of each event's metadata, which
    ``jax.profiler.ProfileData`` does not expose, so this reads the
    ``XSpace`` proto itself: ``planes`` (1) with ``name`` (2),
    ``event_metadata`` (4) and ``stat_metadata`` (5), both maps of id
    (1) to a message (2); an event metadata's ``name`` (2) and ``stats``
    (5); a stat's ``metadata_id`` (1) and its string, held in
    ``str_value`` (5) or, by reference, in the name of the stat metadata
    that ``ref_value`` (7) names."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        parts = list(_fields(plane))
        name = next((v.decode() for f, v in parts if f == 2), "")
        if not name.startswith("/device:"):
            continue
        strings = {}
        for f, v in parts:
            if f == 5:
                meta = dict(_fields(dict(_fields(v))[2]))
                strings[meta.get(1, 0)] = meta.get(2, b"").decode()
        key = next((i for i, n in strings.items() if n == SCOPE_STAT), None)
        paths = out[name] = {}
        for f, v in parts:
            if f != 4 or key is None:
                continue
            meta = list(_fields(dict(_fields(v))[2]))
            for f2, stat in meta:
                st = dict(_fields(stat)) if f2 == 5 else {}
                if st.get(1, 0) == key:
                    value = (st[5].decode() if 5 in st
                             else strings.get(st.get(7), ""))
                    ev = next((n.decode() for f3, n in meta if f3 == 2), "")
                    paths[ev] = value.rsplit(":", 1)[0]
    return out


def extract(path: str) -> dict:
    """``{"devices": {plane: [[name, start_ns, end_ns, phase], ...]},
    "host": [[name, start_ns, end_ns], ...],
    "program": [[name, start_ns, end_ns, chunk], ...]}`` from one xplane
    file; ``chunk`` is None on spans that carry none."""
    from jax.profiler import ProfileData
    scopes = scope_paths(path)
    devices, host, program = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            paths = scopes.get(plane.name, {})
            for line in plane.lines:
                if line.name == tracing.OPS_LINE:
                    devices[plane.name] = [
                        [tracing.op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns,
                         phase_of(paths.get(e.name, ""))]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = [e.name, e.start_ns, e.start_ns + e.duration_ns]
                    if e.name.startswith("bench."):
                        host.append(span)
                    elif e.name.startswith(PROGRAM):
                        program.append(span + [dict(e.stats).get("chunk")])
    return {"devices": devices, "host": host, "program": program}


def _self_times(ops):
    """``(phase, self ns)`` of each operation of one device: its time
    less that of the operations directly inside it."""
    out, open_ = [], []          # open_: [phase, start, end, inner ns]
    for _, s, e, ph in sorted(ops, key=lambda op: (op[1], -op[2])):
        while open_ and open_[-1][2] <= s:
            o = open_.pop()
            out.append((o[0], o[2] - o[1] - o[3]))
        if open_:
            open_[-1][3] += min(e, open_[-1][2]) - s
        open_.append([ph, s, e, 0])
    out.extend((o[0], o[2] - o[1] - o[3]) for o in open_)
    return out


def _innermost(spans, t):
    """The shortest span holding ``t``, or None."""
    best = None
    for sp in spans:
        if sp[1] <= t < sp[2] and (best is None
                                   or sp[2] - sp[1] < best[2] - best[1]):
            best = sp
    return best


def summarize(events: dict, top: int = 10) -> dict:
    """Phase seconds of each device, idle seconds per chunk boundary and
    the longest idle gaps, over the traced window."""
    spans = [h for h in events["host"] if h[0] == tracing.QUERY_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {tracing.QUERY_SPAN} span, found "
                           f"{len(spans)}")
    w0, w1 = spans[0][1], spans[0][2]
    devices = sorted(events["devices"])
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    program = [p for p in events["program"] if p[2] > w0 and p[1] < w1]
    chunk_spans = [p for p in program if p[0] in CHUNK_SPANS]
    chunks = sorted({p[3] for p in chunk_spans})
    phase_s = {ph: [0.0] * len(devices) for ph in PHASES + (OTHER,)}
    chunk_idle = dict.fromkeys(chunks, 0.0)
    gaps = []
    for i, dev in enumerate(devices):
        clipped = [(n, max(s, w0), min(e, w1), ph)
                   for n, s, e, ph in events["devices"][dev]
                   if e > w0 and s < w1]
        for ph, ns in _self_times(clipped):
            phase_s[ph][i] += ns * 1e-9
        merged = tracing._union([[s, e] for _, s, e, _ in clipped])
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            mid = (s + e) / 2
            held = _innermost(chunk_spans, mid)
            if held is not None:
                chunk_idle[held[3]] += (e - s) * 1e-9 / len(devices)
            named = _innermost(program, mid)
            gaps.append((named[0] if named is not None
                         else tracing._span_at(events["host"], mid),
                         (e - s) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return dict(
        devices=devices,
        phase_s=phase_s,
        chunk_idle_s=[[c, chunk_idle[c]] for c in chunks],
        idle_gaps=[[n, t] for n, t in gaps[:top]],
    )
