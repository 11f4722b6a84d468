"""Chip smoke test: BFS through the engine's main path on one TPU.

Runs the paper's smallest RMAT dataset in the Graph500 shape — RMAT-22,
edge factor 16 (both edge directions: 2**22 vertices, 2**26 edges),
generated from a fixed seed — on one paper package of 64 x 64 = 4096
tiles, rooted at the highest-degree vertex, through ``graph/apps.py`` ->
``DataLocalEngine.run`` (the chunked device-resident loop) -> the Pallas
kernels, in three phases:

  a  ``backend="jnp"``, no proxy
  b  ``backend="jnp"``, Table-II proxy, active-set compaction 2
  c  ``backend="pallas"``, Table-II proxy, compaction 0

Every phase must reproduce the oracle's BFS levels exactly; (b) and (c)
must give equal traffic counters and superstep counts, and the compiled
chunk program of (c) must hold Pallas kernels (``tpu_custom_call``), so
no kernel ran interpreted.  One JSON line per phase reports compile
seconds, run seconds, supersteps and the device's peak bytes in use.

``--chips 4`` runs only the distributed engine on four chips
(``DistributedEngine`` over a 4-device ``shard_map`` mesh, synchronous
and double-buffered exchange) against the same oracle.

The last line of standard output is the result,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed.  Without a TPU, or without the
repository next to it, the script exits nonzero and prints no result.

Usage:  python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

SEED = 20230417          # graph generator seed
SCALE = 22               # RMAT scale: 2**22 vertices
EDGE_FACTOR = 16         # Graph500 edge factor
TILES = 4096             # one paper package, 64 x 64 tiles


def _require_tpu():
    """The first device, which must be a TPU: there is no CPU path."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found "
                         f"{dev.platform!r} ({dev.device_kind})")
    return dev


class _CompileClock:
    """Seconds JAX spends lowering and compiling (persistent-cache
    lookups included) while it is active, from JAX's own monitoring
    events."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if self._on and event in self.EVENTS:
            self.seconds += duration

    def __enter__(self):
        self.seconds, self._on = 0.0, True
        return self

    def __exit__(self, *exc):
        self._on = False


def _peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _say(**row) -> None:
    print(json.dumps(row), flush=True)


def _check_levels(name, values, oracle) -> None:
    import numpy as np
    values = np.asarray(values)[: oracle.shape[0]]
    bad = int(np.sum(values != oracle))
    if bad:
        raise AssertionError(f"{name}: {bad} BFS levels differ from the "
                             f"oracle")


def _one_chip(g, grid, root, oracle, dev, clock) -> None:
    import jax.numpy as jnp
    from repro.graph import apps

    proxy = apps.table2_proxy(grid, "bfs")
    phases = (("a", dict(backend="jnp")),
              ("b", dict(backend="jnp", proxy=proxy, compaction=2)),
              ("c", dict(backend="pallas", proxy=proxy, compaction=0)))
    runs = {}
    for name, kw in phases:
        with clock:
            t0 = time.perf_counter()
            res = apps.bfs(g, root, grid, **kw)      # values fetched: synced
            wall = time.perf_counter() - t0
        _check_levels(name, res.values, oracle)
        runs[name] = res.run
        _say(phase=name, backend=kw["backend"],
             proxy=kw.get("proxy") is not None,
             compaction=kw.get("compaction", 0),
             compile_s=clock.seconds, run_s=wall - clock.seconds,
             wall_s=wall, supersteps=res.run.supersteps,
             peak_bytes_in_use=_peak_bytes(dev), levels_equal=True)
    b, c = runs["b"], runs["c"]
    if b.counters.as_dict() != c.counters.as_dict():
        raise AssertionError(f"b/c counters differ: {b.counters.as_dict()} "
                             f"!= {c.counters.as_dict()}")
    if b.supersteps != c.supersteps:
        raise AssertionError(f"b/c supersteps differ: {b.supersteps} != "
                             f"{c.supersteps}")
    # the chunk program phase (c) ran, compiled again (a persistent-cache
    # hit where the cache is on): its kernels must be compiled Mosaic
    # calls, not the interpreter's plain XLA ops
    eng, state, _ = apps.engine_and_state("bfs", g, grid, proxy=proxy,
                                          root=root, backend="pallas")
    zero = jnp.zeros((), jnp.bool_)
    with clock:
        text = eng._chunk.lower(
            eng.graph, state, zero, zero,
            jnp.int32(eng.cfg.max_supersteps),
            length=eng.cfg.run_chunk).compile().as_text()
    n_calls = text.count("tpu_custom_call")
    if not n_calls:
        raise AssertionError("phase c: no tpu_custom_call in the compiled "
                             "chunk program")
    _say(phase="c_program", tpu_custom_calls=n_calls,
         recompile_s=clock.seconds, counters_equal_bc=True)


def _four_chips(g, grid, root, oracle, clock) -> None:
    import jax
    import numpy as np
    from repro.graph import apps

    runs = {}
    for db in (False, True):
        with clock:
            t0 = time.perf_counter()
            eng, state, _ = apps.engine_and_state(
                "bfs", g, grid, root=root, chips=4, backend="shard_map",
                double_buffer=db)
            if eng.mesh.ndev != 4:
                raise AssertionError(f"mesh has {eng.mesh.ndev} devices, "
                                     f"not 4")
            st, run = eng.run(state)
            values = np.asarray(st["values"])
            wall = time.perf_counter() - t0
        _check_levels(f"4chips/db{int(db)}", values, oracle)
        runs[db] = (values, run)
        _say(phase=f"4chips_{'db' if db else 'sync'}", mesh_devices=4,
             compile_s=clock.seconds, run_s=wall - clock.seconds,
             wall_s=wall, supersteps=run.supersteps,
             peak_bytes_in_use=[_peak_bytes(d) for d in jax.devices()],
             levels_equal=True)
    (v0, r0), (v1, r1) = runs[False], runs[True]
    if not np.array_equal(v0, v1):
        raise AssertionError("4 chips: double-buffered values differ")
    if r0.counters.as_dict() != r1.counters.as_dict():
        raise AssertionError("4 chips: double-buffered counters differ")
    if r0.supersteps != r1.supersteps:
        raise AssertionError("4 chips: double-buffered supersteps differ")
    _say(phase="4chips_compare", values_equal=True, counters_equal=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip distributed phase")
    args = ap.parse_args(argv)
    try:
        dev = _require_tpu()
        import jax
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "src"))
        from repro.core.tilegrid import square_grid
        from repro.graph import rmat_edges
        from repro.graph.oracles import bfs_oracle
        from repro.runtime.compile_cache import enable_compile_cache

        cache = enable_compile_cache()
        clock = _CompileClock()
        if args.chips > len(jax.devices()):
            raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                             f"{args.chips} devices, "
                             f"{len(jax.devices())} present")
        t0 = time.perf_counter()
        g = rmat_edges(SCALE, edge_factor=EDGE_FACTOR, seed=SEED,
                       weighted=False)
        grid = square_grid(TILES)
        root = int(g.out_degree().argmax())
        t1 = time.perf_counter()
        oracle = bfs_oracle(g, root)
        t2 = time.perf_counter()
        _say(phase="setup", scale=SCALE, vertices=g.n_rows,
             edges=g.nnz, tiles=grid.num_tiles, root=root,
             root_degree=int(g.out_degree()[root]),
             levels=int(oracle[oracle < float("inf")].max()),
             generate_s=t1 - t0, oracle_s=t2 - t1, compile_cache=cache)
        if args.chips == 4:
            _four_chips(g, grid, root, oracle, clock)
        else:
            _one_chip(g, grid, root, oracle, dev, clock)
        print(json.dumps({"ok": True, "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}}), flush=True)
        return 0
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 2
    except BaseException:
        traceback.print_exc()
        return 1


if __name__ == "__main__":
    sys.exit(main())
