"""Distributed multi-chip runtime for the tile-grid engine (paper §V).

The paper's headline numbers come from *distributed* execution: up to 256
chips — over a million PUs — run the tile grid cooperatively, with
owner-bound updates that leave a chip riding a board-level network
through each package's IO die.  This module is that execution layer:

  * ``partition`` splits a :class:`TileGrid` into a chip grid
    (:class:`ChipPartition`); tile ids, data placement and hop charging
    keep the monolithic engine's global numbering, so results are
    directly comparable.
  * Each chip runs one :class:`DataLocalEngine` superstep over its own
    subgrid per global superstep (the engine kernel is window-parametric
    — see ``core/engine.py``).  Proxy regions and cascade reduction
    trees are adapted chip-locally (``proxy.chip_local_proxy``): the
    cascade root sits at the chip boundary, and anything bound further
    out goes straight to its owner over the off-chip leg.
  * ``exchange`` delivers the boundary mailbox records between
    supersteps.  One step function, written against an
    :class:`~repro.distrib.mesh.ExecMesh`, serves every placement: on a
    real multi-device mesh the exchange is a collective
    (``gather_records`` under ``shard_map``), on a single device the
    mesh helpers degenerate to the identity and the same code is the
    vmapped emulation whose exchange is one combined scatter —
    numerically the same combine, bitwise the same scatter indices.
  * With ``EngineConfig.double_buffer`` the chunked scan carries a
    second mailbox bank: superstep *k* merges flags (the pending
    signal) and stats eagerly but defers the mailbox-*value* scatter to
    the start of superstep *k+1*, so the collective exchange overlaps
    the next superstep's chip-local compute.  Mailbox combining is
    commutative and nothing touches the mailbox between the two fold
    points, so values/counters/trace are bit-identical to the
    synchronous exchange; only the BSP time accumulation changes
    (board + IO-die cycles hidden under the next superstep's compute).
  * Off-chip records are charged a new network leg
    (``netstats.charge_off_chip``): OFF_PKG_PJ_BIT energy per board hop
    and IO-die Rx/Tx latency plus board-link serialization in the BSP
    time model.

Delivery order differs from the monolithic engine only in which records
a mailbox combines first; min-combine apps are therefore bitwise
identical, add-combine apps identical up to f32 re-association.

Like the monolithic engine, the run loop is device-resident: ``run``
scans ``EngineConfig.run_chunk`` whole distributed supersteps (chip
superstep + boundary exchange + stat aggregation) per dispatch — under
``shard_map`` the scan lives *inside* the sharded region, so state
stays device-sharded across the chunk and each iteration's collective
exchange executes on device — and the host checks pending/p_resident
once per chunk (``run(chunk=0)`` keeps the per-step dispatch).
"""
from __future__ import annotations

import dataclasses
import functools
import tempfile
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..checkpoint.ckpt import save_checkpoint
from ..core.costmodel import (CLOCK_GHZ, IO_DIE_RXTX_LAT_NS,
                              PU_OPS_PER_EDGE, PU_OPS_PER_RECORD,
                              _off_pkg_bits_per_cycle,
                              board_link_provisioning, checkpoint_leg_cycles,
                              link_provisioning, recovery_waste_cycles)
from ..runtime.elastic import reshard_checkpoint
from ..runtime.fault import ChipLostError
from ..runtime.straggler import detect_stragglers, rebalance_chunks
from ..core.engine import (INF, AppSpec, DataLocalEngine, EngineConfig,
                           RunResult, _churn_args, _drain_chunked,
                           _legacy_span, _pad,
                           _ProgressReporter, _sanitize_gate, _scan_steps,
                           _stat_keys, bucket_index, chunk_cycles,
                           superstep_counters, superstep_cycles)
from ..core.netstats import MSG_BITS, SuperstepTrace, TrafficCounters
from ..core.proxy import chip_local_proxy
from ..core.tilegrid import ChipPartition, TileGrid, partition_grid
from ..obs.metrics import default_registry
from ..obs.timeline import HostSpan, RunMeta
from .mesh import ExecMesh


# graph arrays partitioned per chip window (the edge arrays stay whole:
# a window's cursors index the global edge list)
_WINDOWED = ("row_lo", "row_hi")


def partition(grid: TileGrid, num_chips: int) -> ChipPartition:
    """Partition ``grid`` into the most square chip grid that divides it."""
    return partition_grid(grid, num_chips)


# --------------------------------------------------------------------------
# boundary exchange
# --------------------------------------------------------------------------
def _owner_slots(part: ChipPartition, chunk_dst: int, dst):
    """Map global dst indices to (owner chip, owner's in-chip tile, local
    mailbox index within that chip).  The single source of the exchanged
    records' mailbox layout — shared by both backends' receive sides."""
    owner = jnp.minimum(dst // chunk_dst, part.grid.num_tiles - 1)
    chip = part.chip_of_tile(owner)
    ltile = part.local_tile(owner)
    return chip, ltile, ltile * chunk_dst + dst % chunk_dst


def _combine_into_mail(mail_val, mail_flag, flat, mask, val, seg, n_seg,
                       is_min):
    """Scatter-combine exchanged records into a flattened mailbox pair.

    ``flat`` indexes the flattened mailbox, ``seg`` the receiving tile
    (for endpoint contention); masked-out records go to a sentinel row.
    Shared by the emulated exchange and the shard_map receive side so
    the two backends cannot drift.  Returns (mail_val, mail_flag, recv)
    where ``recv`` is the per-receiving-tile arrival-count vector
    ``(n_seg,)`` — callers max it for endpoint contention (identical to
    the former recv_max return) and, under telemetry, also reduce it
    per chip for the ``pc_recv`` load vector.
    """
    n_flat = mail_val.shape[0]
    # masked records index one past the end; mode="drop" discards them at
    # the scatter (no padded mailbox copy — see engine._deliver)
    safe = jnp.where(mask, flat, n_flat)
    if is_min:
        mv = mail_val.at[safe].min(jnp.where(mask, val, INF), mode="drop")
    else:
        mv = mail_val.at[safe].add(jnp.where(mask, val, 0.0), mode="drop")
    mf = mail_flag.at[safe].max(mask, mode="drop")
    recv = jax.ops.segment_sum(mask.astype(jnp.float32),
                               jnp.where(mask, seg, n_seg),
                               num_segments=n_seg + 1)[:n_seg]
    return mv, mf, recv


def _merge_flags(mail_flag, flat, mask, seg, n_seg):
    """The *eager* half of the double-buffered exchange: mailbox flags
    (the pending signal) and per-receiving-tile arrival counts merge in
    superstep k itself — only the mailbox-value scatter is deferred to
    the bank.  Identical flag/recv math to :func:`_combine_into_mail`."""
    n_flat = mail_flag.shape[0]
    safe = jnp.where(mask, flat, n_flat)
    mf = mail_flag.at[safe].max(mask, mode="drop")
    recv = jax.ops.segment_sum(mask.astype(jnp.float32),
                               jnp.where(mask, seg, n_seg),
                               num_segments=n_seg + 1)[:n_seg]
    return mf, recv


@jax.named_scope("exchange")
def _fold_bank(state, is_min):
    """Apply the deferred mailbox-value scatter of the double-buffered
    exchange (bank keys ``_db_idx`` / ``_db_val`` / ``_db_mask``) and
    drop the bank from the state dict.

    This is the *same* scatter :func:`_combine_into_mail` would have run
    at the end of the previous superstep, on the *same* mailbox (nothing
    writes ``mail_val`` between the two fold points), so the result is
    bitwise identical for min AND add — deferral only reorders the
    program, not the arithmetic."""
    idx, val, mask = state["_db_idx"], state["_db_val"], state["_db_mask"]
    state = {k: v for k, v in state.items() if not k.startswith("_db_")}
    mv = state["mail_val"].reshape(-1)
    safe = jnp.where(mask, idx, mv.shape[0])
    if is_min:
        mv = mv.at[safe].min(jnp.where(mask, val, INF), mode="drop")
    else:
        mv = mv.at[safe].add(jnp.where(mask, val, 0.0), mode="drop")
    return dict(state, mail_val=mv.reshape(state["mail_val"].shape))


def _pending(state):
    """Live work in a (possibly stacked) engine state — mailbox flags
    plus unfinished edge cursors.  Must be evaluated *after* the
    boundary exchange: a record that crossed chips this superstep is
    pending work even when every chip's pre-exchange queues are empty.
    (The double-buffered exchange merges flags eagerly for exactly this
    reason — a deferred mailbox *value* is never a pending signal.)"""
    return (jnp.sum(state["mail_flag"])
            + jnp.sum(state["cur_hi"] > state["cur_lo"]))


def exchange(part: ChipPartition, chunk_dst: int, state, off, is_min: bool):
    """Deliver per-chip off-chip record buffers into their owner chips'
    mailboxes (the emulated board-level exchange; state is stacked
    ``(chips, ...)``).

    Combining into a mailbox is commutative (min / add / flag-or), so one
    global scatter is exactly equivalent to routing each record across
    the board and combining on arrival.  Returns (state, recv): the
    ``(chips, tiles_local)`` received-record counts, whose max feeds
    endpoint contention in the BSP time model and whose per-chip sums
    feed the ``pc_recv`` telemetry vector.
    """
    C = part.num_chips
    Tl = part.tiles_per_chip
    Nld = Tl * chunk_dst
    dst = off["dst"].reshape(-1)
    val = off["val"].reshape(-1)
    mask = off["mask"].reshape(-1)
    chip, ltile, off_idx = _owner_slots(part, chunk_dst, dst)
    mv, mf, recv = _combine_into_mail(
        state["mail_val"].reshape(-1), state["mail_flag"].reshape(-1),
        chip * Nld + off_idx, mask, val, chip * Tl + ltile, C * Tl, is_min)
    state = dict(state, mail_val=mv.reshape(C, Nld),
                 mail_flag=mf.reshape(C, Nld))
    return state, recv.reshape(C, Tl)


@jax.named_scope("exchange")
def _aggregate(stats, recv, telemetry: bool = False, mesh=None):
    """Reduce per-chip superstep stats to grid-global ones: traffic sums,
    bottleneck (per-tile) maxima; exchange receive contention (``recv``,
    the ``(chips, tiles_local)`` arrival counts, or None on a 1x1
    partition) folds into the delivery max.

    With an :class:`ExecMesh` the local reductions finish as mesh
    collectives (``psum`` / ``pmax``; identity on a single device, so
    ``mesh=None`` and a 1-device mesh are the same arithmetic).

    Under ``telemetry`` the vmapped per-chip/per-tile load vectors are
    additionally reduced to per-chip ``pc_*`` vectors (shape
    ``(chips,)``, all-gathered so the stacked stats channel stays
    replicated) that ride the scan's stacked-dict channel into
    ``obs.imbalance``; the engine's per-tile ``tv_*`` vectors are
    consumed here (a chip's intra-tile split stays chip-local)."""
    ident = lambda x: x                       # noqa: E731
    psum = mesh.psum if mesh is not None else ident
    pmax = mesh.pmax if mesh is not None else ident
    gather = mesh.all_gather if mesh is not None else ident
    agg = {}
    vecs = {}
    for k, v in stats.items():
        if k.startswith("tv_"):
            vecs[k] = v                       # (chips_local, tiles_local)
            continue
        if k in ("compute_per_tile_max", "delivered_max_per_tile",
                 "bucket_cap"):
            agg[k] = pmax(jnp.max(v))
        else:
            agg[k] = psum(jnp.sum(v))
    recv_max = jnp.float32(0.0) if recv is None else pmax(jnp.max(recv))
    agg["delivered_max_per_tile"] = jnp.maximum(
        agg["delivered_max_per_tile"], recv_max)
    if telemetry:
        agg["pc_edges"] = gather(jnp.sum(vecs["tv_edges"], axis=-1))
        agg["pc_records"] = gather(jnp.sum(vecs["tv_records"], axis=-1))
        agg["pc_delivered"] = gather(jnp.sum(vecs["tv_delivered"], axis=-1))
        agg["pc_delivmax"] = gather(jnp.max(vecs["tv_delivered"], axis=-1))
        agg["pc_compute"] = gather(stats["compute_per_tile_max"])
        agg["pc_owner"] = gather(stats["owner_msgs"])
        if "off_chip_msgs" in stats:
            agg["pc_offchip"] = gather(stats["off_chip_msgs"])
        agg["pc_recv"] = (jnp.zeros_like(agg["pc_edges"]) if recv is None
                          else gather(jnp.sum(recv, axis=-1)))
    return agg


# --------------------------------------------------------------------------
class _FaultTolerance:
    """Superstep checkpoint/rollback controller for one ``run()`` call.

    At each host-accounting boundary (per chunk on the chunked loop, per
    superstep on the legacy loop) it polls the fault injector — a raised
    :class:`ChipLostError` unwinds to ``run()``'s retry loop — and, on
    cadence, writes the scan carry through the atomic checkpoint writer
    plus an in-memory snapshot of the host accounting (counters, trace
    length, BSP cycles, in-flight exchange, telemetry sums).

    ``recover()`` rebuilds the :class:`ExecMesh` on the surviving
    devices, restores the carry through ``runtime.elastic``'s
    reshard-on-restore path, rolls the host accounting back to the
    snapshot, and prices every overhead leg (checkpoint writes,
    discarded replay window, re-shard restore) into a *separate*
    accumulator the run adds exactly once at the very end.  Keeping the
    overhead out of the main accumulator is what makes a recovered run
    bit-identical to an unfailed one: the replay re-adds the identical
    floats in the identical order, and the cost model re-prices the
    overhead from the trace's recovery events with the same shared
    helpers (``checkpoint_leg_cycles`` / ``recovery_waste_cycles``), so
    ``reprice_ratio`` stays exactly 1.0.
    """

    def __init__(self, eng, directory, every, injector, counters, trace,
                 prev_exch, overhead, vec_sums, n_board_links):
        self.eng = eng
        self.dir = directory
        self.every = int(every)
        self.injector = injector
        self.counters = counters
        self.trace = trace
        self.prev_exch = prev_exch
        self.overhead = overhead
        self.vec_sums = vec_sums
        self.blinks = n_board_links
        self.pkg = eng.cfg.pkg
        self.grid = eng.cfg.grid
        self.events = trace.recovery_events
        self._snap = None
        self._next = self.every if self.every > 0 else None
        self._bits = None              # carry image size (static shapes)
        self._tmpl = None              # restore template (shape/dtype tree)

    def _image_bits(self, state) -> float:
        if self._bits is None:
            self._bits = 8.0 * (sum(
                int(np.prod(v.shape)) * v.dtype.itemsize
                for v in state.values()) + 1)       # +1: the flush flag
        return self._bits

    def checkpoint(self, steps, state, flush, cycles) -> None:
        """Write the carry at superstep ``steps`` + snapshot accounting."""
        bits = self._image_bits(state)
        host_state = jax.device_get(state)
        if self._tmpl is None:
            self._tmpl = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                          for k, v in host_state.items()}
        flush_b = bool(np.asarray(flush))
        save_checkpoint(
            self.dir, int(steps),
            dict(state=host_state, flush=np.asarray(flush_b)),
            extra_meta=dict(cycles=float(cycles),
                            prev_exch=float(self.prev_exch[0]),
                            overhead=float(self.overhead[0]),
                            counters=self.counters.as_dict()))
        # the write is priced as overhead, never into `cycles`: the main
        # accumulator must replay bit-identically to an unfailed run
        self.overhead[0] += checkpoint_leg_cycles(self.pkg, bits,
                                                  self.blinks)
        self.events.append(dict(kind="checkpoint", step=int(steps),
                                bits=float(bits)))
        self._snap = dict(
            steps=int(steps), flush=flush_b, cycles=float(cycles),
            prev_exch=float(self.prev_exch[0]),
            counters=self.counters.as_dict(),
            vec_sums=(None if self.vec_sums is None else
                      {k: np.array(v, np.float64)
                       for k, v in self.vec_sums.items()}))

    def at_boundary(self, steps, state, flush, done, cycles):
        """The run loop's boundary hook: poll the injector first (so a
        loss at a checkpoint boundary still forces a real rollback),
        then checkpoint on cadence.  Returns ``cycles`` unchanged — the
        hook never perturbs the main accumulator."""
        if self.injector is not None:
            self.injector.poll(int(steps))          # may raise ChipLostError
        if self._next is not None and steps >= self._next and not done:
            self.checkpoint(steps, state, flush, cycles)
            while self._next <= steps:
                self._next += self.every
        return cycles

    def recover(self, err):
        """Chip loss: re-shard onto the survivors + roll back.

        Returns ``(state, flush, steps, cycles)`` for the retry loop to
        resume from the last checkpoint."""
        eng, snap = self.eng, self._snap
        lo, hi = snap["steps"], int(err.at_step)
        # 1. price the discarded window [lo, hi) from the trace rows
        #    BEFORE truncating — with the same vectorized helper the
        #    cost model's replay uses, so both sides sum the identical
        #    floats in the identical order
        self.overhead[0] += recovery_waste_cycles(
            self.pkg, self.grid, self.trace, lo, hi)
        self.events.append(dict(kind="rollback", chip=int(err.chip),
                                from_step=int(lo), at_step=int(hi)))
        # 2. roll host accounting back to the snapshot
        self.trace.truncate(lo)
        for k, v in snap["counters"].items():
            setattr(self.counters, k, v)
        self.counters.supersteps = int(snap["counters"]["supersteps"])
        self.prev_exch[0] = snap["prev_exch"]
        if self.vec_sums is not None:
            self.vec_sums.clear()
            if snap["vec_sums"]:
                self.vec_sums.update(snap["vec_sums"])
        # 3. rebuild the mesh on the survivors; recompiles on next call
        _, new_ndev = eng._drop_device()
        # 4. restore the carry through the elastic reshard path: chip-
        #    stacked leaves re-shard over the surviving device axis
        jmesh = eng.mesh.jax_mesh()

        def rule(path, shape):
            if shape and shape[0] == eng.C and eng.mesh.is_sharded:
                return P(eng.mesh.axis)
            return P()

        restored = reshard_checkpoint(
            self.dir, dict(state=self._tmpl,
                           flush=jax.ShapeDtypeStruct((), np.bool_)),
            jmesh, rule, step=lo)
        state = restored["state"]
        flush = bool(np.asarray(restored["flush"]))
        # 5. the restore streams the carry image back over board links
        self.overhead[0] += checkpoint_leg_cycles(self.pkg, self._bits,
                                                  self.blinks)
        self.events.append(dict(kind="reshard", step=int(lo),
                                bits=float(self._bits),
                                chip=int(err.chip), devices=int(new_ndev)))
        if self._next is not None:
            self._next = lo + self.every
        return state, flush, lo, snap["cycles"]


# --------------------------------------------------------------------------
class DistributedEngine:
    """Multi-chip rendering of :class:`DataLocalEngine`.

    Mirrors the monolithic engine's interface (``init_state`` /
    ``activate_all`` / ``run``) so the six applications run unchanged;
    state is held stacked per chip ``(chips, local...)`` and ``run``
    reassembles ``values`` into global order.
    """

    def __init__(self, app: AppSpec, cfg: EngineConfig,
                 row_lo: np.ndarray, row_hi: np.ndarray,
                 col_idx: np.ndarray, weights: Optional[np.ndarray] = None,
                 part: Optional[ChipPartition] = None,
                 num_chips: Optional[int] = None, backend: str = "auto"):
        grid = cfg.grid
        if part is None:
            if num_chips is None:
                raise ValueError("pass part= or num_chips=")
            part = partition_grid(grid, num_chips)
        if cfg.proxy is not None:
            cfg = dataclasses.replace(
                cfg, proxy=chip_local_proxy(cfg.proxy, part.sub_ny,
                                            part.sub_nx))
        if cfg.backend != "jnp":
            raise ValueError(
                "EngineConfig.backend='pallas' (kernel hot spots) is "
                "monolithic-only; the distributed runtime vmaps the "
                "superstep across chips")
        self.app = app
        self.cfg = cfg
        self.part = part
        self.kernel = DataLocalEngine(app, cfg, row_lo, row_hi, col_idx,
                                      weights, part=part)
        self.C = part.num_chips
        self.Tl = part.tiles_per_chip
        self.Cs, self.Cd = cfg.chunk_src, cfg.chunk_dst
        self._is_min = app.combine == "min"
        # (chip, local) <-> global tile permutations, host-side
        perm = np.concatenate([part.tile_ids(c) for c in range(self.C)])
        self._perm = perm
        inv = np.empty_like(perm)
        inv[perm] = np.arange(perm.shape[0])
        self._inv = inv
        # the graph argument of every step program: per-chip row ranges
        # stacked along the chips axis, the edge arrays whole (replicated
        # on every device)
        self._graph_s = dict(self.kernel.graph)
        for k in _WINDOWED:
            self._graph_s[k] = self._shard(np.asarray(self.kernel.graph[k]),
                                           self.Cs)
        self._graph_sharded = {k: k in _WINDOWED for k in self._graph_s}
        self._graph_axes = {k: 0 if k in _WINDOWED else None
                            for k in self._graph_s}
        self._chip_ids = jnp.arange(self.C, dtype=jnp.int32)
        # device placement: any ndev dividing C works; when the host's
        # device count doesn't divide, ExecMesh falls back to the largest
        # dividing subset with a warning (no hard failure)
        self.mesh = ExecMesh.build(self.C, backend=backend)
        self.backend = self.mesh.backend_name
        self._backend_req = backend
        self.last_load_vecs = None     # summed pc_* vectors of the last run
        # execute the deferred-bank exchange only where there IS an
        # exchange; the cost model's double_buffer flag stays cfg-driven
        self._db_exec = bool(cfg.double_buffer) and self.C > 1
        self._step = None
        self._chunk_fns = {}
        self._stat_names = None        # packed-stat layout, cached
        self._off_len = None           # per-chip off-record buffer length

    # ----------------------------------------------------------- data moves
    def _shard(self, a_global: np.ndarray, chunk: int) -> jnp.ndarray:
        """Global per-index array -> stacked (chips, tiles_local*chunk)."""
        a = np.asarray(a_global).reshape(self.part.grid.num_tiles, chunk)
        return jnp.asarray(a[self._perm].reshape(self.C, self.Tl * chunk))

    def _gather(self, a_stacked, chunk: int) -> np.ndarray:
        """Stacked (chips, tiles_local*chunk) -> global per-index array."""
        a = np.asarray(a_stacked).reshape(self.C * self.Tl, chunk)
        return a[self._inv].reshape(-1)

    # ------------------------------------------------------------- elasticity
    def _drop_device(self) -> tuple:
        """Rebuild the execution mesh on one fewer device (chip loss).

        The logical chip count stays ``self.C`` — the grid partition and
        global tile numbering are placement invariants — only the device
        set hosting the chip blocks shrinks, so the lost chip's block is
        re-hosted by the survivors.  Compiled step/chunk functions are
        mesh-bound and dropped; the packed-stat layout and off-record
        buffer length are mesh-independent and kept.  Returns
        (old_ndev, new_ndev)."""
        old_ndev = self.mesh.ndev
        if old_ndev > 1:
            backend = "vmap" if self._backend_req == "vmap" else "auto"
            self.mesh = ExecMesh.build(self.C, backend=backend,
                                       device_count=old_ndev - 1)
            self.backend = self.mesh.backend_name
            self._step = None
            self._chunk_fns = {}
        return old_ndev, self.mesh.ndev

    # ---------------------------------------------------------------- state
    def init_state(self, seed_idx=None, seed_val=None,
                   values: Optional[np.ndarray] = None):
        with HostSpan("engine.init_state"):
            k = self.kernel
            ident = self.app.identity
            vals_g = (np.full((k.Ngd,), ident, np.float32) if values is None
                      else np.asarray(_pad(np.asarray(values, np.float32),
                                           k.Ngd, ident), np.float32))
            mail_val_g = np.full((k.Ngd,), ident, np.float32)
            mail_flag_g = np.zeros((k.Ngd,), bool)
            self._n_seeds = 0   # mailbox seeds: the sanitizer's consumed-bound
            if seed_idx is not None:
                si = np.atleast_1d(np.asarray(seed_idx)).astype(np.int64)
                sv = np.atleast_1d(np.asarray(seed_val)).astype(np.float32)
                mail_val_g[si] = sv
                mail_flag_g[si] = True
                self._n_seeds = int(si.shape[0])
            st = dict(
                values=self._shard(vals_g, self.Cd),
                mail_val=self._shard(mail_val_g, self.Cd),
                mail_flag=self._shard(mail_flag_g, self.Cd),
                cur_lo=jnp.zeros((self.C, k.Ns), jnp.int32),
                cur_hi=jnp.zeros((self.C, k.Ns), jnp.int32),
                cur_val=jnp.zeros((self.C, k.Ns), jnp.float32),
            )
            if self.cfg.proxy is not None:
                S = self.cfg.proxy.slots
                st["p_tag"] = jnp.full((self.C, self.Tl, S), -1, jnp.int32)
                st["p_val"] = jnp.full((self.C, self.Tl, S), ident,
                                       jnp.float32)
            return st

    def activate_all(self, state, cur_val):
        state = dict(state)
        state["cur_lo"] = self._graph_s["row_lo"]
        state["cur_hi"] = self._graph_s["row_hi"]
        state["cur_val"] = self._shard(
            _pad(np.asarray(cur_val, np.float32), self.kernel.Ngs, 0.0),
            self.Cs)
        return state

    # ---------------------------------------------------------------- steps
    def _get_step(self):
        """Legacy per-superstep dispatch (always the synchronous
        exchange: one host sync per superstep hides nothing anyway)."""
        if self._step is None:
            mesh = self.mesh
            step = self._raw_step(mesh)

            def fn(graph, state, flush):
                return step(graph, state, mesh.chip_ids(), flush)

            jstep = mesh.shard_jit(
                fn, in_specs=(self._graph_sharded, True, False),
                out_specs=(True, False))
            self._step = lambda state, flush: jstep(self._graph_s, state,
                                                    flush)
        return self._step

    def _get_chunk_fn(self, length: int):
        """Chunked (scan-of-supersteps) dispatch on the mesh; one
        compiled function per chunk length, cached."""
        if length not in self._chunk_fns:
            self._chunk_fns[length] = self._make_chunk(length)
        return self._chunk_fns[length]

    @property
    def _write_back(self) -> bool:
        return self.cfg.proxy is not None and self.cfg.proxy.write_back

    def _raw_vmap_step(self):
        """The unified step on a single-device (identity) mesh — what
        the analysis passes abstract-trace and the stat-layout probe
        uses; bitwise the chips-axis emulation regardless of the mesh
        the engine itself runs on."""
        return self._raw_step(ExecMesh(self.C, 1))

    def _raw_step(self, mesh: ExecMesh, double_buffer: bool = False):
        """One whole distributed superstep against ``mesh`` (vmapped
        chips per device + boundary exchange + stat aggregation),
        unjitted — the one body every dispatch shares.  On a sharded
        mesh it must execute inside the mesh's ``chips`` axis; on a
        single-device mesh every collective is the identity and the
        function is plain-traceable.

        ``double_buffer`` defers the exchanged mailbox-*value* scatter
        into a ``_db_*`` bank in the carried state (folded in at the
        start of the next superstep — see :func:`_fold_bank`); flags,
        arrival counts and all stats still merge eagerly, so pending
        and the recorded trace are identical to the synchronous path."""
        kernel, part, Cd, Tl = self.kernel, self.part, self.Cd, self.Tl
        is_min = self._is_min
        Nld = kernel.Nd
        per = mesh.per
        telemetry = self.cfg.telemetry
        multi = self.C > 1
        ladder = kernel._ladder
        # compacted buckets pad their off-chip buffers to the dense
        # length, so all switch branches (and the double-buffer bank)
        # share one shape
        pad_off = (self._off_record_len()
                   if multi and len(ladder) > 1 else None)

        graph_axes = self._graph_axes

        def step(graph, state, chip_ids, flush):
            if double_buffer:
                # previous superstep's deferred exchange lands first —
                # the same scatter, one superstep later (the mailbox is
                # untouched in between), overlapping this compute
                state = _fold_bank(state, is_min)
            if len(ladder) > 1:
                # per-device bucket selection: the switch index is the
                # *unbatched* max over this device's chips, so exactly
                # one pre-traced branch executes per device (a per-chip
                # index under vmap would run every branch); flags merge
                # eagerly under double_buffer, so the post-fold mask is
                # the true pending signal
                active = jax.vmap(kernel._active_tiles)(state)
                n_act = jnp.sum(active.astype(jnp.int32), axis=1)
                idx = bucket_index(jnp.max(n_act), ladder)

                def branch(w):
                    def run(st, act):
                        return jax.vmap(
                            functools.partial(kernel.chip_superstep,
                                              window=w, pad_off_to=pad_off),
                            in_axes=(graph_axes, 0, 0, None, 0))(
                            graph, st, chip_ids, flush, act)
                    return run

                new_state, stats, off = jax.lax.switch(
                    idx, [branch(None if j == 0 else cap)
                          for j, cap in enumerate(ladder)], state, active)
                stats = dict(
                    stats, active_tiles=n_act.astype(jnp.float32),
                    bucket_cap=jnp.full((per,), jnp.take(
                        jnp.asarray(ladder, jnp.float32), idx)))
            else:
                new_state, stats, off = jax.vmap(
                    kernel.chip_superstep,
                    in_axes=(graph_axes, 0, 0, None))(
                    graph, state, chip_ids, flush)
            if multi:
                # board-level exchange: every chip gathers the full
                # off-chip record stream and keeps what it owns
                # (collective all-to-all without per-destination packing,
                # so hub skew cannot overflow a send buffer; identity
                # gather on one device — the stacked stream is already
                # global and the scatter indices match the emulation)
                with jax.named_scope("exchange"):
                    g_dst, g_val, g_mask = mesh.gather_records(
                        (off["dst"].reshape(-1), off["val"].reshape(-1),
                         off["mask"].reshape(-1)))
                    ochip, ltile, off_idx = _owner_slots(part, Cd, g_dst)
                    mine = g_mask & (ochip // per == mesh.axis_index())
                    lane = ochip % per
                    flat = lane * Nld + off_idx
                    seg = lane * Tl + ltile
                    if double_buffer:
                        mf, recv = _merge_flags(
                            new_state["mail_flag"].reshape(-1), flat, mine,
                            seg, per * Tl)
                        new_state = dict(new_state,
                                         mail_flag=mf.reshape(per, Nld),
                                         _db_idx=flat, _db_val=g_val,
                                         _db_mask=mine)
                    else:
                        mv, mf, recv = _combine_into_mail(
                            new_state["mail_val"].reshape(-1),
                            new_state["mail_flag"].reshape(-1),
                            flat, mine, g_val, seg, per * Tl, is_min)
                        new_state = dict(new_state,
                                         mail_val=mv.reshape(per, Nld),
                                         mail_flag=mf.reshape(per, Nld))
                    recv = recv.reshape(per, Tl)
            else:                       # 1x1 partition: nothing can leave
                recv = None
            agg = _aggregate(stats, recv, telemetry, mesh)
            # pending must see the post-exchange mailbox flags: a record
            # that crossed chips this superstep is the next superstep's
            # work (flags merge eagerly even when double-buffered)
            with jax.named_scope("exchange"):
                agg["pending"] = mesh.psum(_pending(new_state))
            return new_state, agg

        return step

    def _off_record_len(self) -> int:
        """Per-chip off-chip record-buffer length (static: OQ emissions
        plus proxy flush legs), via abstract eval of the superstep —
        sizes the double-buffer bank."""
        if self._off_len is None:
            k = self.kernel
            st = {
                "values": jax.ShapeDtypeStruct((self.C, k.Nd), jnp.float32),
                "mail_val": jax.ShapeDtypeStruct((self.C, k.Nd),
                                                 jnp.float32),
                "mail_flag": jax.ShapeDtypeStruct((self.C, k.Nd), jnp.bool_),
                "cur_lo": jax.ShapeDtypeStruct((self.C, k.Ns), jnp.int32),
                "cur_hi": jax.ShapeDtypeStruct((self.C, k.Ns), jnp.int32),
                "cur_val": jax.ShapeDtypeStruct((self.C, k.Ns), jnp.float32),
            }
            if self.cfg.proxy is not None:
                S = self.cfg.proxy.slots
                st["p_tag"] = jax.ShapeDtypeStruct((self.C, self.Tl, S),
                                                   jnp.int32)
                st["p_val"] = jax.ShapeDtypeStruct((self.C, self.Tl, S),
                                                   jnp.float32)
            off = jax.eval_shape(
                lambda s: jax.vmap(k.chip_superstep,
                                   in_axes=(self._graph_axes, 0, 0, None))(
                    self._graph_s, s, self._chip_ids,
                    jnp.zeros((), jnp.bool_))[2],
                st)
            self._off_len = int(off["dst"].shape[1])
        return self._off_len

    def _make_chunk(self, length: int):
        mesh = self.mesh
        db = self._db_exec
        step = self._raw_step(mesh, double_buffer=db)
        write_back = self._write_back
        is_min = self._is_min
        # the bank holds the gathered global record stream (same shape on
        # every device at any ndev)
        bank_len = self.C * self._off_record_len() if db else 0

        def fn(graph, state, flush, done, left):
            # the scan lives *inside* the sharded region: state stays
            # device-sharded across the whole chunk and each iteration's
            # collective exchange/psum executes on device — the host only
            # sees the per-chunk carry and the stacked (replicated) stats
            chip_ids = mesh.chip_ids()
            if db:
                # empty bank entering the chunk (the previous chunk
                # drained its own); the bank lives only inside this
                # function, so specs/carry crossing the host are unchanged
                state = dict(state,
                             _db_idx=jnp.zeros((bank_len,), jnp.int32),
                             _db_val=jnp.zeros((bank_len,), jnp.float32),
                             _db_mask=jnp.zeros((bank_len,), bool))
            carry, out = _scan_steps(
                lambda st, fl: step(graph, st, chip_ids, fl),
                state, flush, done, left, length, write_back)
            if db:
                st, fl2, dn, lf = carry
                carry = (_fold_bank(st, is_min), fl2, dn, lf)
            return carry, out

        jfn = mesh.shard_jit(
            fn, in_specs=(self._graph_sharded, True, False, False, False),
            out_specs=((True, False, False, False), False))
        return lambda state, flush, done, left: jfn(
            self._graph_s, state, flush, done, left)

    # ------------------------------------------------------------------ run
    def run(self, state, max_supersteps: Optional[int] = None,
            progress_every: int = 0, chunk: Optional[int] = None,
            observer=None, fault_injector=None,
            ckpt_dir: Optional[str] = None):
        """Run distributed supersteps until drained; returns
        (state-with-global-values, RunResult).

        Like the monolithic engine, the loop is device-resident:
        ``chunk`` supersteps (default ``EngineConfig.run_chunk``) run per
        dispatch — each including its boundary exchange — and the host
        checks pending/p_resident once per chunk.  ``chunk=0`` keeps the
        legacy per-superstep dispatch.  ``progress_every`` reports at
        chunk granularity with true executed superstep counts.

        ``observer`` (obs.timeline.Observer) hooks the existing chunk
        host-accounting boundary exactly like the monolithic engine —
        zero extra host syncs, bit-identical results; with
        ``EngineConfig.telemetry`` the spans carry per-chip ``pc_*``
        load vectors.

        Fault tolerance: with ``EngineConfig.ckpt_every_supersteps > 0``
        the scan carry is checkpointed at the same boundaries (cadence
        in supersteps, zero extra host syncs — the carry is already on
        the host's side of the sync).  ``fault_injector``
        (runtime.fault.FaultInjector) injects a chip loss mid-run; the
        engine re-shards onto the surviving devices, rolls back to the
        last checkpoint and replays — final values, counters, supersteps
        and trace are bit-identical to an unfailed run, with all
        recovery overhead priced separately (see trace.recovery_events).
        ``ckpt_dir`` overrides the checkpoint directory (default: a
        fresh temp dir per run)."""
        with HostSpan("engine.run_start"):
            cfg, part = self.cfg, self.part
            maxs = max_supersteps or cfg.max_supersteps
            K = cfg.run_chunk if chunk is None else int(chunk)
            if observer is not None:
                observer.on_run_start(RunMeta(
                    app=self.app.name, grid_ny=cfg.grid.ny,
                    grid_nx=cfg.grid.nx, n_chips=self.C,
                    chips_y=part.chips_y, chips_x=part.chips_x, chunk=K,
                    backend=self.backend, sanitize=cfg.sanitize,
                    telemetry=cfg.telemetry, pkg=cfg.pkg, grid=cfg.grid,
                    n_devices=self.mesh.ndev))
            counters = TrafficCounters()
            cycles = 0.0
            steps = 0
            pkg = cfg.pkg
            links = link_provisioning(cfg.grid, pkg)
            cy, cx = part.chips_y, part.chips_x
            # board links provisioned under the run's own PackageConfig
            # (the per-axis knobs) — shared formula with costmodel's
            # re-pricing so pricing the trace under this config
            # reproduces this run's time
            n_board_links = board_link_provisioning(pkg, cy, cx)
            board_div = n_board_links * _off_pkg_bits_per_cycle(pkg)
            db = bool(cfg.double_buffer)
            trace = SuperstepTrace(board_links=n_board_links,
                                   chips_y=cy, chips_x=cx, double_buffer=db)
            io_lat_cycles = 2.0 * IO_DIE_RXTX_LAT_NS * CLOCK_GHZ  # Tx + Rx
            fill = links["diameter"] * 0.5                     # pipeline fill
            # double-buffer accounting: the exchange leg (board
            # serialization + IO-die latency) of the previous charged
            # superstep, still in flight while this superstep computes;
            # the final one drains in the open (tail charge after the
            # loop).  Stays 0.0 synchronous.
            prev_exch = [0.0]
            # recovery overhead (checkpoint legs, discarded replay
            # windows, re-shard restores) accumulates apart from `cycles`
            # and is added exactly once after the drain tail — see
            # _FaultTolerance
            overhead = [0.0]
            vec_sums = {} if cfg.telemetry else None
            ft = None
            if cfg.ckpt_every_supersteps > 0 or fault_injector is not None:
                ft = _FaultTolerance(
                    self,
                    directory=(ckpt_dir or tempfile.mkdtemp(
                        prefix=f"repro_ckpt_{self.app.name}_")),
                    every=cfg.ckpt_every_supersteps,
                    injector=fault_injector, counters=counters,
                    trace=trace, prev_exch=prev_exch, overhead=overhead,
                    vec_sums=vec_sums, n_board_links=n_board_links)

            boundary = None
            if ft is not None:
                if K <= 0:
                    def boundary(bsteps, bstate, bflush, bdone):
                        nonlocal cycles
                        cycles = ft.at_boundary(bsteps, bstate, bflush,
                                                bdone, cycles)
                else:
                    boundary = ft.at_boundary
                ft.checkpoint(0, state, False, cycles)   # step-0 baseline
            if K > 0:
                progress = _ProgressReporter(
                    f"{self.app.name}/{self.C}chips", progress_every,
                    sanitize=cfg.sanitize, tiles=self.C * self.Tl)
                # stat layout of the packed scan rows (the vmapped step's
                # agg carries the same keys the shard_map rendering emits)
                if self._stat_names is None:   # one abstract trace per engine
                    raw = self._raw_vmap_step()
                    self._stat_names = _stat_keys(
                        lambda st, fl: raw(self._graph_s, st,
                                           self._chip_ids, fl),
                        state, jnp.zeros((), jnp.bool_))
                chunk_fn = self._get_chunk_fn(K)

        def account(stats):
            """Legacy-loop per-superstep accounting.  The chunked branch
            uses the vectorized twin (add_chunk_cycles below with
            chunk_counters/append_chunk in _drain_chunked) AND
            costmodel._trace_time_s_parsed replays both rules from the
            trace — edit ALL in lockstep; tests/test_chunked.py and the
            reprice contract are the bit-identity gates."""
            nonlocal cycles
            _sanitize_gate(cfg, self.app.name,
                           float(stats.get("sanity_violations", 0.0)))
            counters.add(superstep_counters(stats))
            trace.append_step(stats, element_bits=cfg.element_bits)
            if vec_sums is not None:
                for k, v in stats.items():
                    if k.startswith("pc_"):
                        vec_sums[k] = (vec_sums.get(k, 0.0)
                                       + np.asarray(v, np.float64))
            # ---- BSP time model: monolithic levels + the board-level leg
            t_board = float(stats.get("off_chip_hop_msgs", 0.0)) * MSG_BITS / (
                n_board_links * _off_pkg_bits_per_cycle(pkg))
            core = superstep_cycles(stats, pkg, links)
            if db:
                # overlap-aware: this superstep pays max(its chip-local
                # work, the previous exchange); its own exchange hides
                # under the next superstep
                if core > 0 or t_board > 0 or stats["pending"] > 0:
                    cycles += max(core, prev_exch[0]) + fill
                    prev_exch[0] = t_board + (
                        io_lat_cycles
                        if stats.get("off_chip_msgs", 0.0) > 0 else 0.0)
            else:
                step_cycles = max(core, t_board)
                if step_cycles > 0 or stats["pending"] > 0:
                    cycles += step_cycles + fill
                    if stats.get("off_chip_msgs", 0.0) > 0:
                        cycles += io_lat_cycles

        def add_chunk_cycles(stacked, n_act, cycles):
            # monolithic BSP terms maxed with the board leg, plus IO-die
            # latency on supersteps with off-chip records -- accumulated
            # in execution order like the legacy loop (double-buffered:
            # each superstep pays max(chip-local work, previous
            # exchange), its exchange carries over)
            if cfg.sanitize:
                bad = stacked.get("sanity_violations")
                if bad is not None:
                    _sanitize_gate(cfg, self.app.name,
                                   float(np.sum(bad[:n_act])))

            def offvec(key):           # absent on a 1x1 partition
                a = stacked.get(key)
                return (np.asarray(a[:n_act], np.float64)
                        if a is not None else np.zeros(n_act))

            t_board = offvec("off_chip_hop_msgs") * MSG_BITS / board_div
            core = chunk_cycles(stacked, n_act, pkg, links)
            pend = np.asarray(stacked["pending"][:n_act])
            offm = offvec("off_chip_msgs")
            if db:
                for c, b, p, o in zip(core.tolist(), t_board.tolist(),
                                      pend.tolist(), offm.tolist()):
                    if c > 0 or b > 0 or p > 0:
                        cycles += max(c, prev_exch[0]) + fill
                        prev_exch[0] = b + (io_lat_cycles if o > 0
                                            else 0.0)
                return cycles
            sc = np.maximum(core, t_board)
            for s, p, o in zip(sc.tolist(), pend.tolist(), offm.tolist()):
                if s > 0 or p > 0:
                    cycles += s + fill
                    if o > 0:
                        cycles += io_lat_cycles
            return cycles

        steps0, flush0 = 0, False
        while True:
            try:
                if K <= 0:
                    state, steps = self._run_legacy(
                        state, maxs, progress_every, account,
                        observer=observer, steps0=steps0, flush0=flush0,
                        boundary=boundary)
                else:
                    state, steps, cycles = _drain_chunked(
                        chunk_fn, state, maxs, self._stat_names, counters,
                        trace, cfg.element_bits, progress, add_chunk_cycles,
                        cycles, observer=observer, steps0=steps0,
                        flush0=flush0, boundary=boundary,
                        vec_sums=vec_sums)
                break
            except ChipLostError as e:
                state, flush0, steps0, cycles = ft.recover(e)
                if K > 0:
                    # the recovery rebuilt the mesh: re-bind the compiled
                    # chunk fn
                    chunk_fn = self._get_chunk_fn(K)
        with HostSpan("engine.finish"):
            cycles += prev_exch[0]   # final in-flight exchange drains open
            cycles += overhead[0]    # recovery legs, priced once at the end
            counters.supersteps = steps
            self.last_load_vecs = vec_sums
            time_s = cycles / (CLOCK_GHZ * 1e9)
            out_state = dict(state)
            out_state["values"] = self._gather(state["values"], self.Cd)
            result = RunResult(counters=counters, cycles=cycles,
                               time_s=time_s, supersteps=steps, trace=trace)
            if cfg.sanitize:
                from ..analysis import invariants as _inv
                findings = _inv.check_run(
                    result, pkg=pkg, grid=cfg.grid,
                    where=f"sanitize/{self.app.name}/{self.C}chips",
                    write_back=self._write_back,
                    seeds=getattr(self, "_n_seeds", 0),
                    drained=steps < maxs)
                _inv.assert_clean(
                    findings,
                    context=f"run({self.app.name}, {self.C} chips)")
            if observer is not None:
                observer.on_run_end(result)
        return out_state, result

    def _run_legacy(self, state, maxs, progress_every, account,
                    observer=None, *, steps0=0, flush0=False,
                    boundary=None):
        """The seed per-superstep dispatch loop (one host sync per
        superstep) — the measured baseline for the chunked loop.  With an
        ``observer``, each superstep emits one single-step span at the
        per-step host sync this loop already pays.

        ``steps0``/``flush0`` resume mid-run from a checkpoint;
        ``boundary(steps, state, flush, done)`` hooks the per-superstep
        host sync (fault injection + checkpoint cadence) at the point
        where the loop's continue/break decision is already known."""
        write_back = self._write_back
        step_fn = self._get_step()
        sync_ctr = default_registry().counter("engine.host_syncs")
        steps = int(steps0)
        flush_flag = jnp.asarray(bool(flush0))
        while steps < maxs:
            at = dict(chunk=steps, step=steps)
            with HostSpan("engine.dispatch", **at) as t_dispatch:
                state, stats = step_fn(state, flush_flag)
            with HostSpan("engine.fetch", **at) as t_fetch:
                stats = jax.device_get(stats)
                sync_ctr.inc()
            steps += 1
            with HostSpan("engine.account", **at,
                          **_churn_args(stats)) as t_account:
                account(stats)
            if observer is not None:
                observer.on_chunk(_legacy_span(steps, stats, t_dispatch.t,
                                               t_fetch.t, t_account.t))
            if flush_flag:
                flush_flag = jnp.asarray(False)
            pending_zero = stats["pending"] == 0
            want_flush = bool(pending_zero and write_back
                              and stats["p_resident"] > 0)
            if want_flush:
                flush_flag = jnp.asarray(True)
            done = pending_zero and not want_flush
            if boundary is not None:
                # sees the NEXT iteration's flush flag, so a checkpoint
                # taken here resumes with the correct write-back phase
                with HostSpan("engine.boundary", **at):
                    boundary(steps, state, flush_flag, done)
            if done:
                break
            if want_flush:
                continue
            if progress_every and steps % progress_every == 0:
                print(f"  [{self.app.name}/{self.C}chips] step {steps} "
                      f"pending={stats['pending']:.0f}")
        return state, steps

    # ---------------------------------------------------- straggler handling
    def rebalance_plan(self, n_items: Optional[int] = None,
                       max_ratio: float = 1.5, threshold: float = 2.0):
        """Straggler-aware ownership re-chunking plan for the next wave.

        Feeds the last run's accumulated per-chip ``pc_*`` telemetry
        (requires ``EngineConfig.telemetry``) into ``runtime.straggler``:
        per-chip load is modeled in PU ops — edges streamed plus records
        drained (the cost model's ``PU_OPS_PER_EDGE`` /
        ``PU_OPS_PER_RECORD``) plus exchange arrivals — and
        ``rebalance_chunks`` returns new destination-range boundaries
        over ``n_items`` (default: the global destination index space).
        Purely advisory between query waves: applying it re-partitions
        ownership for the *next* run, never perturbing the current one,
        so every wave stays bit-exact.  Returns a dict with the measured
        load, straggler mask/imbalance ratio, new boundaries, and the
        predicted post-rebalance imbalance."""
        v = self.last_load_vecs
        if not v:
            raise ValueError(
                "no per-chip load telemetry: run() with "
                "EngineConfig.telemetry=True before rebalance_plan()")
        zero = np.zeros(self.C, np.float64)
        load = (np.asarray(v.get("pc_edges", zero), np.float64)
                * PU_OPS_PER_EDGE
                + np.asarray(v.get("pc_records", zero), np.float64)
                * PU_OPS_PER_RECORD
                + np.asarray(v.get("pc_recv", zero), np.float64))
        mask, ratio = detect_stragglers(load, threshold=threshold)
        n = int(self.part.grid.num_tiles * self.Cd
                if n_items is None else n_items)
        bounds = rebalance_chunks(load, n, max_ratio=max_ratio)
        # predicted post-rebalance load: piecewise-uniform density over
        # the old equal chunks, integrated over the new boundaries
        eq = n / self.C
        cum = np.concatenate([[0.0], np.cumsum(load)])
        new_load = np.diff(np.interp(bounds, np.arange(self.C + 1) * eq,
                                     cum))
        pred = float(new_load.max() / max(new_load.mean(), 1e-9))
        return dict(load=load, stragglers=mask, imbalance=float(ratio),
                    boundaries=bounds, predicted_imbalance=pred)


# --------------------------------------------------------------------------
def run_distributed(app: AppSpec, cfg: EngineConfig, row_lo, row_hi, col_idx,
                    weights=None, *, chips: Optional[int] = None,
                    part: Optional[ChipPartition] = None,
                    backend: str = "auto", seed_idx=None, seed_val=None,
                    values=None, activate=None,
                    max_supersteps: Optional[int] = None):
    """One-call distributed run: partition, seed/activate, run to drain.

    Returns (global values array, RunResult).  ``activate`` (a global
    per-source value array) selects epoch-style activation
    (PageRank/SPMV/Histogram); ``seed_idx``/``seed_val`` seed mailboxes
    (BFS/SSSP/WCC).
    """
    eng = DistributedEngine(app, cfg, row_lo, row_hi, col_idx, weights,
                            part=part, num_chips=chips, backend=backend)
    state = eng.init_state(seed_idx=seed_idx, seed_val=seed_val,
                           values=values)
    if activate is not None:
        state = eng.activate_all(state, activate)
    state, run = eng.run(state, max_supersteps)
    return state["values"], run
