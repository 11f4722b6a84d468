"""The data-local execution engine (paper §II-B, §III), TPU-adapted.

Execution model
---------------
The dataset is scattered across tiles as equal chunks.  Work proceeds in
*supersteps* (the TPU-idiomatic, bulk-synchronous rendering of the
paper's asynchronous task pipeline — see DESIGN.md §2):

  1. **IQ drain**: each tile consumes up to ``iq_cap`` pending records
     from its *mailbox* (a dense, per-owned-index combining input queue —
     incoming records with the same index are combined on arrival, which
     is exactly what the paper's combining queues/P$ exploit: all
     evaluated apps have commutative updates).  Unconsumed records remain
     pending — measurable backpressure.
  2. **Task execution / OQ emit**: consuming an improving record
     re-activates the per-item edge cursor; each tile then streams up to
     ``oq_cap`` edges from its active cursors (the paper's PU executing
     tasks, with the OQ bounding per-superstep emission), producing
     (dst_index, value) records.
  3. **Proxy stage** (if configured): records are routed to the proxy
     tile in the sender's region, batch-coalesced, filtered/combined
     through a direct-mapped P$ with write-through or write-back policy,
     and only surviving records are forwarded to the true owners.
  3b. **Cascaded drain** (if the proxy config carries a ``CascadeConfig``):
     instead of travelling straight to the owner, every record the proxy
     stage forwards — write-through survivors, write-back evictions and
     whole-P$ flushes alike — climbs a *region reduction tree*: the
     record hops from its region proxy to the proxy for the same index
     in the enclosing super-region (base regions grouped
     ``group_ny x group_nx`` per level), where records from sibling
     regions bound for the same index are combined into one, then
     onward level-by-level until the tree root forwards a single record
     to the true owner.  Under the *selective* criterion a record whose
     owner already lies inside its current super-region exits the tree
     early and goes straight to the owner, and apps whose combine is not
     profitable to merge (``AppSpec.cascade_profitable=False``) skip the
     tree entirely.  This is the paper's scaling mechanism: owner-bound
     updates are combined hierarchically instead of all converging on
     one tile, so cross-chip traffic shrinks as the grid grows.
  4. **Delivery**: surviving records are combined into owner mailboxes.

Every message is charged exact XY-torus hops at each leg (including every
cascade-tree leg); the BSP time model takes the per-superstep max over
(tile compute, per-level network serialization, endpoint contention —
including contention at intermediate cascade proxies) — reproducing the
paper's observable effects without per-cycle router simulation.

Device-resident run loop
------------------------
The paper's runs take hundreds of thousands of supersteps, so the run
loop must not pay a host round-trip per superstep.  ``run`` therefore
executes ``EngineConfig.run_chunk`` supersteps per device dispatch with
``jax.lax.scan``: the engine state, the write-back flush flag and the
drained/budget flags ride the scan carry entirely on device, each
superstep's fixed-shape stats are stacked into a ``(K, ...)`` trace
buffer, and the host fetches that buffer — and checks ``pending`` /
``p_resident`` — once per chunk instead of once per step.  Flush
triggering and termination are decided *inside* the scan body (the same
rules the legacy loop applied between dispatches), and supersteps past
the stop point are masked no-ops, so counters and traces are
bit-identical to the per-step loop while host syncs drop from
O(supersteps) to O(supersteps / K).  ``run(chunk=0)`` keeps the legacy
per-step loop (the benchmark baseline); larger ``run_chunk`` amortizes
dispatch further at the cost of up to K-1 wasted (masked) supersteps in
the final chunk — ``benchmarks/engine_throughput.py`` measures the
tradeoff.  Per-superstep traces are reassembled on the host from the
stacked chunk stats (``SuperstepTrace.append_chunk``), in execution
order, exactly as the per-step loop appended them.

Hot-spot kernels: with ``EngineConfig.backend="pallas"`` the engine's
combine/drain hot spots — the IQ-drain relax, the P$ / cascade segment
min/add, and the owner-mailbox delivery — run through the Pallas kernels
in ``kernels/`` (``relax_min``, ``segment_combine``, ``deliver_fused``);
the default ``"jnp"`` path is the numerical oracle the Pallas path is
tested against (bitwise for min-combine apps, up to f32 re-association
for add).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import netstats
from ..obs.metrics import default_registry
from ..obs.timeline import ChunkSpan, HostSpan, RunMeta
from .costmodel import (CLOCK_GHZ, PU_OPS_PER_EDGE, PU_OPS_PER_RECORD, DCRA_SRAM,
                        PackageConfig, link_provisioning, step_cycles)
from .netstats import MSG_BITS, SuperstepTrace, TrafficCounters
from .proxy import (ProxyConfig, cascade_proxy_tile, make_pcache,
                    pcache_slot, proxy_tile)
from .tilegrid import ChipPartition, TileGrid

INF = jnp.float32(jnp.inf)
# edge values that read the per-edge weight array
_WEIGHTED_EDGE_VALUES = ("add_w", "mul_w")


@dataclasses.dataclass(frozen=True)
class AppSpec:
    """How an application maps onto the engine."""

    name: str
    combine: str             # 'min' | 'add'
    edge_value: str          # 'add_w' | 'add_one' | 'mul_w' | 'carry' | 'one'
    reactivate: bool = True  # mailbox improvements re-activate edge cursors
    count_teps_on: str = "edges"   # what Graph500-style TEPS counts
    # Whether merging two in-flight updates to the same index into one
    # record is profitable for this app (true for commutative reductions
    # like min/add).  The selective-cascading criterion consults this:
    # with CascadeConfig(selective=True), unprofitable apps bypass the
    # reduction tree and forward proxy output straight to the owners.
    cascade_profitable: bool = True

    @property
    def identity(self) -> float:
        return float("inf") if self.combine == "min" else 0.0


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    grid: TileGrid
    n_src: int                       # items with edge cursors (vertices/cols/elems)
    n_dst: int                       # items receiving updates (vertices/rows/bins)
    oq_cap: int = 64                 # edge emissions per tile per superstep
    iq_ratio: int = 8                # iq_cap = iq_ratio * oq_cap
    proxy: Optional[ProxyConfig] = None
    pkg: PackageConfig = DCRA_SRAM
    max_supersteps: int = 200_000
    element_bits: int = 64           # index+value footprint per dataset element
    # Supersteps per device dispatch: the run loop scans this many
    # supersteps on device between host syncs (0 = legacy per-step loop).
    run_chunk: int = 16
    # 'jnp' (oracle) or 'pallas': which implementation the combine/drain
    # hot spots (IQ drain, segment min/add, owner delivery) run through.
    backend: str = "jnp"
    # Runtime sanitizer (repro.analysis): every superstep additionally
    # counts invariant violations on device (monotone relaxation for
    # min-combine apps, mailbox flag/value consistency, NaNs) into a
    # ``sanity_violations`` stat the run loop raises on, and the run's
    # counters/trace are conservation-checked after draining
    # (``analysis.invariants.check_run``).  Results are bit-identical to
    # sanitize=False — the checks only observe; failures raise
    # ``analysis.invariants.SanitizerError``.
    sanitize: bool = False
    # Telemetry vectors (repro.obs): every superstep additionally emits
    # per-tile load vectors (``tv_edges`` / ``tv_records`` /
    # ``tv_delivered``; the distributed driver reduces them to per-chip
    # ``pc_*`` vectors) that ride the existing chunk fetch — zero extra
    # host syncs — and feed ``obs.imbalance`` / the Perfetto tracks.
    # Results are bit-identical to telemetry=False: the vectors are
    # extra *outputs*, never inputs, of the superstep.
    telemetry: bool = False
    # Double-buffered boundary exchange (distributed runtime): superstep
    # k's board-level mailbox-value delivery is deferred into a second
    # mailbox bank and folded in at the start of superstep k+1, so the
    # collective exchange overlaps the next superstep's chip-local
    # compute.  Mailbox combining is commutative and nothing touches the
    # mailbox between the two fold points, so counters/trace/values are
    # bit-identical to the synchronous exchange — only the BSP time
    # accumulation changes (exchange cycles hidden under compute; see
    # costmodel._trace_time_s_parsed).  Monolithic runs have no board
    # exchange: the flag only tags their trace, time is unchanged.
    double_buffer: bool = False
    # Active-set compaction (0 = off): depth of the power-of-two window
    # capacity ladder.  With compaction=L the superstep is pre-traced
    # once per capacity in ``capacity_ladder(T, L)`` (T, T/4, ..., down
    # L rungs); each superstep counts the active tiles (pending mailbox
    # flags or open edge cursors) *on device* and ``lax.switch``es into
    # the smallest window that fits — zero added host syncs, the IQ/OQ
    # record stream shrinks from T*oq_cap to W*oq_cap rows.  Inactive
    # tiles contribute combine-identity work in the dense path, so every
    # bucket is bit-identical in values, counters and SuperstepTrace to
    # compaction=0 (the oracle; tests/test_compaction.py is the gate).
    compaction: int = 0
    # Fault tolerance (distributed runtime; 0 = off): checkpoint the
    # chunked-scan carry every this-many supersteps, at the chunk
    # host-accounting boundary the run loop already pays (zero extra
    # host syncs), through the atomic ``checkpoint/ckpt.py`` writer.  On
    # an injected chip loss (``runtime.fault.FaultInjector``) the run
    # re-shards the lost device's chip block onto the surviving devices
    # (``ExecMesh`` rebuild + ``runtime.elastic.reshard_checkpoint``),
    # rolls host accounting back to the snapshot and replays — final
    # values/counters/trace/supersteps are bit-identical to an unfailed
    # run, and the checkpoint/rollback/re-shard overhead is priced into
    # ``time_s`` so the reprice contract still holds exactly
    # (``costmodel.checkpoint_leg_cycles`` / ``recovery_waste_cycles``).
    ckpt_every_supersteps: int = 0

    @property
    def iq_cap(self) -> int:
        return self.iq_ratio * self.oq_cap

    @property
    def chunk_src(self) -> int:
        return self.grid.chunk_size(self.n_src)

    @property
    def chunk_dst(self) -> int:
        return self.grid.chunk_size(self.n_dst)


class DataLocalEngine:
    """Vectorised single-host engine: simulates the whole tile grid, with
    exact traffic accounting.  (The sharded multi-device rendering of the
    same schedule lives in ``core/collectives.py`` + ``launch/dryrun.py``.)

    The superstep kernel is *window-parametric*: with the default
    ``part=None`` the window is the whole grid (the monolithic engine);
    with a ``ChipPartition`` the same kernel executes one chip's subgrid
    at a time — local state, global tile ids and data indices — which is
    how ``distrib.driver`` runs one engine superstep per chip (vmapped or
    under ``shard_map``) and exchanges the off-window records between
    supersteps.
    """

    def __init__(self, app: AppSpec, cfg: EngineConfig,
                 row_lo: np.ndarray, row_hi: np.ndarray,
                 col_idx: np.ndarray, weights: Optional[np.ndarray] = None,
                 part: Optional[ChipPartition] = None):
        if cfg.backend not in ("jnp", "pallas"):
            raise ValueError(f"unknown engine backend {cfg.backend!r}")
        self.app = app
        self.cfg = cfg
        grid = cfg.grid
        self.part = part if part is not None else ChipPartition(grid, 1, 1)
        self.n_chips = self.part.num_chips
        T = self.part.tiles_per_chip      # tiles per execution window
        self.T = T
        self.Tg = grid.num_tiles          # tiles in the whole grid
        self.Cs = cfg.chunk_src
        self.Cd = cfg.chunk_dst
        self.Ns = T * self.Cs             # window lengths (mono: == global)
        self.Nd = T * self.Cd
        self.Ngs = self.Tg * self.Cs      # global lengths / index sentinels
        self.Ngd = self.Tg * self.Cd
        self._cascade_levels = 0
        if cfg.proxy is not None:
            if T * cfg.proxy.slots >= 2**31:
                raise ValueError("T*slots must fit int32 for P$ sort keys")
            cfg.proxy.validate_window(self.part.sub_ny, self.part.sub_nx)
            casc = cfg.proxy.cascade
            if casc is not None and (not casc.selective
                                     or app.cascade_profitable):
                self._cascade_levels = casc.levels
        self._ladder = capacity_ladder(T, cfg.compaction)
        # The device-resident graph, passed to every jitted program as an
        # *argument* (a closed-over array would be embedded in the
        # program as a constant: compile time and program size would
        # grow with nnz).  Per-source arrays are padded to the *global*
        # length; in chip mode the driver partitions row_lo/row_hi into
        # per-window slices before stepping.  Weights are uploaded only
        # for apps whose edge value reads them.
        self.graph = dict(
            row_lo=jnp.asarray(_pad(row_lo, self.Ngs, 0), jnp.int32),
            row_hi=jnp.asarray(_pad(row_hi, self.Ngs, 0), jnp.int32),
            col_idx=jnp.asarray(col_idx, jnp.int32))
        if app.edge_value in _WEIGHTED_EDGE_VALUES:
            if weights is None:
                weights = np.ones_like(col_idx, dtype=np.float32)
            self.graph["weights"] = jnp.asarray(weights, jnp.float32)
        self._superstep = jax.jit(self._superstep_impl)
        self._chunk = jax.jit(self._chunk_impl, static_argnames=("length",))
        self._stat_names = None        # packed-stat layout, cached per engine
        self._n_seeds = 0              # set by init_state, read by sanitizer

    def chip_superstep(self, graph, state, chip_id, flush,
                       active=None, window=None, pad_off_to=None):
        """One superstep of window ``chip_id``: pure in its array args so
        the distributed driver can vmap / shard_map it across chips.
        ``graph`` is :attr:`graph` with ``row_lo``/``row_hi`` sliced to
        the window.
        Returns (new_state, stats, off) where ``off`` is the dict of
        off-chip records (dst, val, mask) to exchange — ``None`` for a
        monolithic window.

        ``window=W`` (with ``active``, the (T,) active-tile mask) runs
        the active-set-compacted superstep: the IQ/OQ stages execute on
        a W-row compacted window, bit-identical to the dense path (the
        inactive tiles it skips are combine-identity no-ops).
        ``pad_off_to`` pads the off-chip record buffer with masked
        sentinels to the dense length so every compaction bucket of a
        ``lax.switch`` returns identical shapes (and the double-buffer
        bank size is unchanged)."""
        return self._step(graph, state, chip_id, flush,
                          active=active, window=window,
                          pad_off_to=pad_off_to)

    def _require_mono(self, what: str):
        """init_state/activate_all/run build whole-grid state; with a
        multi-chip partition the per-window shapes differ and state
        handling lives in the driver."""
        if self.n_chips > 1:
            raise ValueError(
                f"{what} is monolithic-only; with a {self.n_chips}-chip "
                f"partition use distrib.DistributedEngine, which wraps "
                f"this engine's chip_superstep")

    # ---------------------------------------------------------------- state
    def init_state(self, seed_idx=None, seed_val=None,
                   values: Optional[np.ndarray] = None):
        self._require_mono("init_state")
        with HostSpan("engine.init_state"):
            ident = jnp.float32(self.app.identity)
            st = dict(
                values=jnp.full((self.Nd,), ident) if values is None
                else jnp.asarray(_pad(values, self.Nd, self.app.identity),
                                 jnp.float32),
                mail_val=jnp.full((self.Nd,), ident),
                mail_flag=jnp.zeros((self.Nd,), jnp.bool_),
                cur_lo=jnp.zeros((self.Ns,), jnp.int32),
                cur_hi=jnp.zeros((self.Ns,), jnp.int32),
                cur_val=jnp.zeros((self.Ns,), jnp.float32),
            )
            if self.cfg.proxy is not None:
                tags, vals = make_pcache(self.cfg.grid, self.cfg.proxy,
                                         self.app.identity)
                st["p_tag"], st["p_val"] = tags, vals
            self._n_seeds = 0   # mailbox seeds: the sanitizer's consumed-bound
            if seed_idx is not None:
                si = jnp.asarray(np.atleast_1d(seed_idx), jnp.int32)
                sv = jnp.asarray(np.atleast_1d(seed_val), jnp.float32)
                st["mail_val"] = st["mail_val"].at[si].set(sv)
                st["mail_flag"] = st["mail_flag"].at[si].set(True)
                self._n_seeds = int(si.shape[0])
            return st

    def activate_all(self, state, cur_val):
        """Epoch-style activation (PageRank/SPMV/Histogram): every source
        item starts with its full edge range and a carried value."""
        self._require_mono("activate_all")
        state = dict(state)
        state["cur_lo"] = self.graph["row_lo"]
        state["cur_hi"] = self.graph["row_hi"]
        state["cur_val"] = jnp.asarray(_pad(cur_val, self.Ns, 0.0), jnp.float32)
        return state

    # ------------------------------------------------------------ superstep
    def _superstep_impl(self, graph, state, flush: jnp.ndarray):
        """Monolithic superstep: the whole grid as one window."""
        return self._step_mono(graph, state, flush)

    def _step_mono(self, graph, state, flush):
        """One monolithic superstep, dispatched through the compaction
        ladder: with ``compaction=0`` this is exactly the dense
        ``_step``; otherwise the active-tile count (computed on device
        from the carry — no host sync) picks the smallest pre-traced
        window branch via ``lax.switch``.  Every branch is bit-identical
        to the dense path; the extra ``active_tiles`` / ``bucket_cap``
        stats are pure telemetry outputs the fixed-key counter/trace
        accumulators ignore."""
        if len(self._ladder) <= 1:
            new_state, stats, _ = self._step(graph, state, jnp.int32(0),
                                             flush)
            return new_state, stats
        active = self._active_tiles(state)
        n_act = jnp.sum(active.astype(jnp.int32))
        idx = bucket_index(n_act, self._ladder)

        def branch(w):
            def run(st, fl, act):
                return self._step(graph, st, jnp.int32(0), fl, active=act,
                                  window=w)
            return run

        new_state, stats, _ = jax.lax.switch(
            idx, [branch(None if j == 0 else cap)
                  for j, cap in enumerate(self._ladder)],
            state, flush, active)
        stats = dict(stats, active_tiles=n_act.astype(jnp.float32),
                     bucket_cap=jnp.take(
                         jnp.asarray(self._ladder, jnp.float32), idx))
        return new_state, stats

    def _active_tiles(self, state):
        """(T,) mask of tiles with pending mailbox records or open edge
        cursors — the exact set the dense superstep does non-identity
        work on (reactivation only touches flagged tiles, so post-drain
        emission stays inside this set too)."""
        T = self.T
        mail = jnp.any(state["mail_flag"].reshape(T, self.Cd), axis=1)
        cur = jnp.any((state["cur_hi"] > state["cur_lo"])
                      .reshape(T, self.Cs), axis=1)
        return mail | cur

    def _edge_value(self, graph, cval, pos):
        """Per-edge record value from the source cursor value and the
        edge position (shared by the dense and compacted emit fronts)."""
        app = self.app
        if app.edge_value == "add_w":
            return cval + graph["weights"][pos]
        if app.edge_value == "add_one":
            return cval + 1.0
        if app.edge_value == "mul_w":
            return cval * graph["weights"][pos]
        if app.edge_value == "carry":
            return cval
        if app.edge_value == "one":
            return jnp.ones_like(cval)
        raise ValueError(app.edge_value)

    @jax.named_scope("front")
    def _front_dense(self, graph, state, tile_gids):
        """Dense IQ drain + OQ emit over all T tiles (the oracle path).

        Returns (new_vals, mail_val, mail_flag, cur_lo, cur_hi, cur_val,
        consumed_vec, edges_vec, consumed_full, edges_full, dst, cand,
        emit_mask, src_tile, churn): full-length state arrays, per-lane
        count vectors (here lane == tile), their (T,) per-tile
        renderings, the flattened emission record stream, and the
        superstep's ``(reactivations, stale_edges)`` (see :func:`_churn`)."""
        app, cfg = self.app, self.cfg
        T, Cs, Cd = self.T, self.Cs, self.Cd
        is_min = app.combine == "min"
        ident = jnp.float32(app.identity)

        # ---- 1. IQ drain (budgeted mailbox consumption) -------------------
        flag2d = state["mail_flag"].reshape(T, Cd)
        csum = jnp.cumsum(flag2d.astype(jnp.int32), axis=1)
        take2d = flag2d & (csum <= cfg.iq_cap)
        take = take2d.reshape(-1)
        mval, vals = state["mail_val"], state["values"]
        if cfg.backend == "pallas":
            # fused relax kernel: combine + improvement detection in one
            # VMEM pass (same formulas as the jnp oracle below)
            from ..kernels import ops as kops
            new_vals, imp8 = kops.relax(vals, mval, take, combine=app.combine)
            improved = imp8.astype(bool)
        elif is_min:
            improved = take & (mval < vals)
            new_vals = jnp.where(improved, mval, vals)
        else:
            improved = take
            new_vals = jnp.where(take, vals + mval, vals)
        mail_flag = state["mail_flag"] & ~take
        mail_val = jnp.where(take, ident, mval)
        consumed_per_tile = jnp.sum(take2d, axis=1)

        cur_lo, cur_hi, cur_val = state["cur_lo"], state["cur_hi"], state["cur_val"]
        churn = _NO_CHURN
        if app.reactivate:
            # an improving record restarts the item's edge cursor with the
            # new value (re-expansion of an already-visited item is the
            # engine's rendering of data staleness: measurable wasted work).
            re = improved[: self.Ns] if self.Nd == self.Ns else jnp.zeros(
                (self.Ns,), jnp.bool_)
            churn = _churn(re, vals[: self.Ns], ident, cur_lo,
                           graph["row_lo"])
            cur_lo = jnp.where(re, graph["row_lo"], cur_lo)
            cur_hi = jnp.where(re, graph["row_hi"], cur_hi)
            cur_val = jnp.where(re, new_vals[: self.Ns], cur_val)

        # ---- 2. OQ emit (budgeted edge streaming) -------------------------
        B = cfg.oq_cap
        rem2d = (cur_hi - cur_lo).reshape(T, Cs)
        prefix = jnp.cumsum(rem2d, axis=1)                    # inclusive
        capped = jnp.minimum(prefix, B)
        take_v2d = capped - jnp.concatenate(
            [jnp.zeros((T, 1), jnp.int32), capped[:, :-1]], axis=1)
        total_take = capped[:, -1]                            # (T,)
        pos, lane_val, emit_mask = _emit_lanes(
            capped, take_v2d, cur_lo.reshape(T, Cs), cur_val.reshape(T, Cs), B)
        col_idx = graph["col_idx"]
        pos = jnp.clip(pos, 0, col_idx.shape[0] - 1)
        dst = col_idx[pos]
        cand = self._edge_value(graph, lane_val, pos)
        cur_lo = cur_lo + (take_v2d.reshape(-1))

        # flatten records (tile ids are global; dst indices are global)
        R = T * B
        dst = dst.reshape(R)
        cand = cand.reshape(R)
        emit_mask = emit_mask.reshape(R)
        src_tile = jnp.repeat(tile_gids, B)
        return (new_vals, mail_val, mail_flag, cur_lo, cur_hi, cur_val,
                consumed_per_tile, total_take, consumed_per_tile,
                total_take, dst, cand, emit_mask, src_tile, churn)

    @jax.named_scope("front")
    def _front_compact(self, graph, state, chip_id, active, W):
        """Compacted IQ drain + OQ emit over a W-tile active window.

        Active tiles are compacted (stably, preserving tile order) into
        the leading rows of a W-row window; every IQ/OQ tensor op then
        runs on (W, .) gathers instead of (T, .) and the emission record
        stream shrinks to W*oq_cap rows.  Invalid window lanes gather
        tile T-1's rows, so their mailbox flags and cursor ranges are
        forced to zero — otherwise an *active* tile T-1 would be drained
        and emitted twice — making them combine-identity no-ops, and the
        scatter-back drops them (sentinel row T, ``mode="drop"``).  Live
        records keep the dense path's tile-major relative order, so the
        downstream sorts, segment reductions and delivery scatters see
        the same live sequence: state, counters and trace stay
        bit-identical to ``_front_dense``.  Same return contract as
        ``_front_dense`` (per-lane count vectors are (W,); the (T,)
        renderings are scattered back only under telemetry)."""
        app, cfg = self.app, self.cfg
        T, Cs, Cd = self.T, self.Cs, self.Cd
        is_min = app.combine == "min"
        ident = jnp.float32(app.identity)
        w_valid, w_rows, rows_drop = _compact_window(active, W, T)

        # ---- 1. IQ drain on the window's mailbox rows ---------------------
        flagW2 = (state["mail_flag"].reshape(T, Cd)[w_rows]
                  & w_valid[:, None])
        csum = jnp.cumsum(flagW2.astype(jnp.int32), axis=1)
        takeW2 = flagW2 & (csum <= cfg.iq_cap)
        takeW = takeW2.reshape(-1)
        mval2 = state["mail_val"].reshape(T, Cd)
        vals2 = state["values"].reshape(T, Cd)
        mvalW = mval2[w_rows].reshape(-1)
        valsW = vals2[w_rows].reshape(-1)
        if cfg.backend == "pallas":
            from ..kernels import ops as kops
            nvW, imp8 = kops.relax(valsW, mvalW, takeW, combine=app.combine)
            improvedW = imp8.astype(bool)
        elif is_min:
            improvedW = takeW & (mvalW < valsW)
            nvW = jnp.where(improvedW, mvalW, valsW)
        else:
            improvedW = takeW
            nvW = jnp.where(takeW, valsW + mvalW, valsW)
        mail_flagW = flagW2.reshape(-1) & ~takeW
        mail_valW = jnp.where(takeW, ident, mvalW)
        consumedW = jnp.sum(takeW2, axis=1)

        # ---- cursors, windowed --------------------------------------------
        cur_lo2 = state["cur_lo"].reshape(T, Cs)
        cur_loW = cur_lo2[w_rows].reshape(-1)
        cur_hiW = state["cur_hi"].reshape(T, Cs)[w_rows].reshape(-1)
        cur_valW = state["cur_val"].reshape(T, Cs)[w_rows].reshape(-1)
        react = app.reactivate and self.Nd == self.Ns
        churn = _NO_CHURN
        if react:
            # Cd == Cs here, so ``improvedW`` is laid out exactly like
            # the windowed cursor rows (the dense path's improved[:Ns])
            row_loW = graph["row_lo"].reshape(T, Cs)[w_rows].reshape(-1)
            row_hiW = graph["row_hi"].reshape(T, Cs)[w_rows].reshape(-1)
            churn = _churn(improvedW, valsW, ident, cur_loW, row_loW)
            cur_loW = jnp.where(improvedW, row_loW, cur_loW)
            cur_hiW = jnp.where(improvedW, row_hiW, cur_hiW)
            cur_valW = jnp.where(improvedW, nvW, cur_valW)

        # ---- 2. OQ emit from the window -----------------------------------
        B = cfg.oq_cap
        rem2d = jnp.where(w_valid[:, None],
                          (cur_hiW - cur_loW).reshape(W, Cs), 0)
        prefix = jnp.cumsum(rem2d, axis=1)                    # inclusive
        capped = jnp.minimum(prefix, B)
        take_v2d = capped - jnp.concatenate(
            [jnp.zeros((W, 1), jnp.int32), capped[:, :-1]], axis=1)
        total_take = capped[:, -1]                            # (W,)
        pos, lane_val, emit_mask = _emit_lanes(
            capped, take_v2d, cur_loW.reshape(W, Cs), cur_valW.reshape(W, Cs), B)
        col_idx = graph["col_idx"]
        pos = jnp.clip(pos, 0, col_idx.shape[0] - 1)
        dst = col_idx[pos]
        cand = self._edge_value(graph, lane_val, pos)
        cur_loW = cur_loW + (take_v2d.reshape(-1))

        # ---- ONE fused (W, .) scatter-back for the whole state ------------
        # Scatter cost on XLA CPU is per update ROW, so the six per-array
        # scatter-backs are stacked side by side into a single W-row
        # scatter.  Everything rides as f32 *bits*: the mailbox flag as
        # 0.0/1.0 (the != 0 reconstruction is exact), the int32 cursor
        # bounds bitcast (concat/scatter-set/slice are pure data movement
        # — no arithmetic touches the lanes, so the round-trip is
        # bit-exact for any pattern), values/mail_val/cur_val untouched.
        bc_f = lambda a: jax.lax.bitcast_convert_type(a, jnp.float32)
        bc_i = lambda a: jax.lax.bitcast_convert_type(a, jnp.int32)
        parts_T = [vals2, mval2,
                   state["mail_flag"].reshape(T, Cd).astype(jnp.float32),
                   bc_f(cur_lo2)]
        parts_W = [nvW.reshape(W, Cd), mail_valW.reshape(W, Cd),
                   mail_flagW.reshape(W, Cd).astype(jnp.float32),
                   bc_f(cur_loW.reshape(W, Cs))]
        if react:
            parts_T += [bc_f(state["cur_hi"].reshape(T, Cs)),
                        state["cur_val"].reshape(T, Cs)]
            parts_W += [bc_f(cur_hiW.reshape(W, Cs)),
                        cur_valW.reshape(W, Cs)]
        stacked = jnp.concatenate(parts_T, axis=1).at[rows_drop].set(
            jnp.concatenate(parts_W, axis=1), mode="drop")
        new_vals = stacked[:, :Cd].reshape(-1)
        mail_val = stacked[:, Cd:2 * Cd].reshape(-1)
        mail_flag = (stacked[:, 2 * Cd:3 * Cd] != 0).reshape(-1)
        c0 = 3 * Cd
        cur_lo = bc_i(stacked[:, c0:c0 + Cs]).reshape(-1)
        if react:
            cur_hi = bc_i(stacked[:, c0 + Cs:c0 + 2 * Cs]).reshape(-1)
            cur_val = stacked[:, c0 + 2 * Cs:c0 + 3 * Cs].reshape(-1)
        else:
            cur_hi, cur_val = state["cur_hi"], state["cur_val"]

        # flatten records (tile ids are global; dst indices are global)
        R = W * B
        dst = dst.reshape(R)
        cand = cand.reshape(R)
        emit_mask = emit_mask.reshape(R)
        src_tile = jnp.repeat(self.part.global_tile(chip_id, w_rows), B)
        if cfg.telemetry:    # (T,) per-tile renderings for the tv_* vectors
            consumed_full = jnp.zeros((T,), consumedW.dtype).at[rows_drop] \
                .set(consumedW, mode="drop")
            edges_full = jnp.zeros((T,), total_take.dtype).at[rows_drop] \
                .set(total_take, mode="drop")
        else:
            consumed_full = edges_full = None
        return (new_vals, mail_val, mail_flag, cur_lo, cur_hi, cur_val,
                consumedW, total_take, consumed_full, edges_full, dst,
                cand, emit_mask, src_tile, churn)

    def _step(self, graph, state, chip_id, flush, active=None,
              window=None, pad_off_to=None):
        app, cfg, grid = self.app, self.cfg, self.cfg.grid
        T, Cs, Cd = self.T, self.Cs, self.Cd
        is_min = app.combine == "min"
        ident = jnp.float32(app.identity)
        tile_gids = self.part.global_tile(
            chip_id, jnp.arange(T, dtype=jnp.int32))

        if window is None:
            (new_vals, mail_val, mail_flag, cur_lo, cur_hi, cur_val,
             consumed_vec, edges_vec, consumed_per_tile, edges_per_tile,
             dst, cand, emit_mask, src_tile, churn) = self._front_dense(
                graph, state, tile_gids)
        else:
            if active is None:
                active = self._active_tiles(state)
            (new_vals, mail_val, mail_flag, cur_lo, cur_hi, cur_val,
             consumed_vec, edges_vec, consumed_per_tile, edges_per_tile,
             dst, cand, emit_mask, src_tile, churn) = self._front_compact(
                graph, state, chip_id, active, window)
        vals = state["values"]
        owner = jnp.minimum(dst // Cd, self.Tg - 1)

        # per-lane maxima/sums equal the dense per-tile ones: compacted
        # lanes cover every tile with nonzero work, and the counts the
        # window drops are exact zeros (max over non-negatives, sums)
        stats = dict(edges_processed=jnp.sum(edges_vec),
                     records_consumed=jnp.sum(consumed_vec),
                     reactivations=churn[0], stale_edges=churn[1],
                     compute_per_tile_max=jnp.max(
                         consumed_vec * PU_OPS_PER_RECORD
                         + edges_vec * PU_OPS_PER_EDGE),
                     filtered_at_proxy=jnp.float32(0.0),
                     coalesced_at_proxy=jnp.float32(0.0),
                     cascade_combined=jnp.float32(0.0))

        p_tag = state.get("p_tag")
        p_val = state.get("p_val")

        if cfg.proxy is None:
            (mail_val, mail_flag, owner_leg, off_ch, per_tile,
             off) = self._drain_to_owners(
                mail_val, mail_flag, dst, cand, emit_mask, src_tile,
                chip_id, None, is_min)
            dmax = jnp.max(per_tile)
            charges = dict(netstats.merge_charges(owner_leg, off_ch),
                           owner_msgs=owner_leg["messages"],
                           owner_hop_msgs=owner_leg["hop_msgs"])
        else:
            (mail_val, mail_flag, p_tag, p_val, charges, pstats, dmax,
             off) = self._proxy_stage(
                mail_val, mail_flag, p_tag, p_val, dst, cand, emit_mask,
                src_tile, owner, flush, is_min, ident, chip_id, tile_gids)
            stats.update(pstats)

        # ---- P$ flush (write-back): emit all resident entries to owners --
        new_state = dict(values=new_vals, mail_val=mail_val,
                         mail_flag=mail_flag, cur_lo=cur_lo, cur_hi=cur_hi,
                         cur_val=cur_val)
        if p_tag is not None:
            new_state["p_tag"], new_state["p_val"] = p_tag, p_val

        pending = (jnp.sum(new_state["mail_flag"])
                   + jnp.sum(new_state["cur_hi"] > new_state["cur_lo"]))
        stats["pending"] = pending
        # write-back P$ residency is *deferred* work: it does not keep the
        # engine busy, but must be flushed before the result is final.
        if p_tag is not None and self.cfg.proxy.write_back:
            stats["p_resident"] = jnp.sum(new_state["p_tag"] >= 0)
        else:
            stats["p_resident"] = jnp.int32(0)
        stats["delivered_max_per_tile"] = dmax
        stats.update({k: jnp.asarray(v, jnp.float32) for k, v in charges.items()})
        if cfg.telemetry:
            # per-tile load vectors (window-local), pure extra outputs:
            # they ride the chunk stat fetch (obs.timeline) and feed
            # obs.imbalance; the distributed driver reduces them to
            # per-chip pc_* vectors in _aggregate.  The proxy stage set
            # tv_delivered already (its delivery vector is internal).
            stats["tv_edges"] = edges_per_tile.astype(jnp.float32)
            stats["tv_records"] = consumed_per_tile.astype(jnp.float32)
            if "tv_delivered" not in stats:
                stats["tv_delivered"] = per_tile.astype(jnp.float32)
        if cfg.sanitize:
            # On-device sanitizer: count invariant violations this
            # superstep (checkify-style — observed, not branched on, so
            # the computation is unchanged).  The run loop raises
            # SanitizerError on a nonzero count.  Saturated f32: the
            # stat rides the packed row and only zero/nonzero matters.
            bad = jnp.int32(0)
            if is_min:
                # relaxation is monotone: a value may never increase
                bad += jnp.sum((new_vals > vals).astype(jnp.int32))
            # an unflagged mailbox slot must hold the combine identity
            bad += jnp.sum((~new_state["mail_flag"]
                            & (new_state["mail_val"] != ident))
                           .astype(jnp.int32))
            # edge cursors may never go negative-length
            bad += jnp.sum((new_state["cur_hi"]
                            < new_state["cur_lo"]).astype(jnp.int32))
            bad += jnp.sum(jnp.isnan(new_state["values"])
                           .astype(jnp.int32))
            stats["sanity_violations"] = jnp.minimum(
                bad, 2 ** 20).astype(jnp.float32)
        if off is not None and pad_off_to is not None:
            # pad the off-chip buffer with masked sentinels to the dense
            # length so every compaction bucket returns identical shapes
            # (masked rows are dropped at the exchange scatter; the live
            # records keep their order, so delivery is bit-identical)
            pad = int(pad_off_to) - off["dst"].shape[0]
            if pad > 0:
                off = dict(
                    dst=jnp.concatenate(
                        [off["dst"],
                         jnp.full((pad,), self.Ngd, jnp.int32)]),
                    val=jnp.concatenate(
                        [off["val"], jnp.full((pad,), ident, jnp.float32)]),
                    mask=jnp.concatenate(
                        [off["mask"], jnp.zeros((pad,), jnp.bool_)]))
        return new_state, stats, off

    # ------------------------------------------------------- owner delivery
    @jax.named_scope("delivery")
    def _drain_to_owners(self, mail_val, mail_flag, dst, val, mask, src,
                         chip_id, region_dims, is_min):
        """Charge the owner-bound leg, deliver on-window records into the
        local mailboxes, and split off-window records for the exchange.

        ``dst``/``src`` are global; the local mailbox index of an
        on-window record is recovered from the owner's in-chip position.
        Returns (mail_val, mail_flag, owner_leg_charge, off_chip_charge,
        delivered_per_tile, off_records) — ``delivered_per_tile`` is the
        (T,) count vector (callers max it into endpoint contention, or
        sum it across delivery legs of the same superstep first);
        ``off_records`` is None for a monolithic window (nothing can
        leave it).
        """
        part, Cd = self.part, self.Cd
        owner = jnp.minimum(dst // Cd, self.Tg - 1)
        owner_leg = netstats.charge(self.cfg.grid, src, owner, mask,
                                    region_dims=region_dims)
        if self.n_chips == 1:
            mail_val, mail_flag, per_tile = _deliver(
                mail_val, mail_flag, dst, val, mask, owner, self.T,
                self.Nd, is_min, backend=self.cfg.backend)
            return mail_val, mail_flag, owner_leg, {}, per_tile, None
        on_chip = part.chip_of_tile(owner) == chip_id
        on = mask & on_chip
        off_mask = mask & ~on_chip
        lowner = part.local_tile(owner)
        ldst = lowner * Cd + dst % Cd
        mail_val, mail_flag, per_tile = _deliver(
            mail_val, mail_flag, ldst, val, on, lowner, self.T, self.Nd,
            is_min, backend=self.cfg.backend)
        off_ch = netstats.charge_off_chip(part, src, owner, off_mask)
        off = dict(dst=jnp.where(off_mask, dst, self.Ngd), val=val,
                   mask=off_mask)
        return mail_val, mail_flag, owner_leg, off_ch, per_tile, off

    # --------------------------------------------------------- proxy stage
    @jax.named_scope("proxy")
    def _proxy_stage(self, mail_val, mail_flag, p_tag, p_val, dst, cand,
                     emit_mask, src_tile, owner, flush, is_min, ident,
                     chip_id, tile_gids):
        cfg, grid = self.cfg, self.cfg.grid
        pcfg = cfg.proxy
        T = self.T
        S = pcfg.slots

        ptile = proxy_tile(grid, pcfg, owner, src_tile)
        leg1 = netstats.charge(grid, src_tile, ptile, emit_mask)
        # the sender's region is window-local by construction, so the
        # proxy tile always lies on this chip — index P$ by local tile.
        ptile_l = self.part.local_tile(ptile)

        slot = pcache_slot(pcfg, dst)
        key = jnp.where(emit_mask, ptile_l * S + slot, T * S)  # sentinel at end
        dkey = jnp.where(emit_mask, dst, self.Ngd)
        (skey, sdst, smask, (scand,),
         new_slot, new_dst, gid) = _lex_group(key, dkey, T * S, cand,
                                              stable=not is_min)
        gagg = self._segment_reduce(scand, smask, gid, is_min)
        combined = gagg[gid]                                   # per-record view
        n_leaders = jnp.sum(new_dst)
        coalesced = jnp.sum(smask) - n_leaders

        winner = new_slot                                      # first dst-group per slot
        bypass = new_dst & ~new_slot                           # batch slot conflicts

        wtile = jnp.minimum(skey // S, T - 1)
        wslot = skey % S
        cur_tag = p_tag[wtile, wslot]
        cur_pv = p_val[wtile, wslot]
        tag_hit = winner & (cur_tag == sdst)
        if is_min:
            improves = combined < cur_pv
        else:
            improves = jnp.ones_like(cur_pv, dtype=bool)
        filtered = tag_hit & ~improves                         # absorbed
        upd_hit = tag_hit & improves
        miss = winner & ~tag_hit
        evict = miss & (cur_tag >= 0) & pcfg.write_back        # flush resident

        if is_min:
            new_pv_hit = jnp.minimum(cur_pv, combined)
        else:
            new_pv_hit = cur_pv + combined
        inst_val = jnp.where(upd_hit, new_pv_hit, combined)
        do_write = upd_hit | miss
        # Scatter P$ updates.  Only winner records write, and there is at
        # most one winner per (tile, slot) per superstep; non-writers are
        # redirected one row past the end and dropped at the scatter
        # (mode="drop"), so no duplicate index can clobber a winner's
        # write (XLA scatter order with dupes is undefined) and the P$ is
        # never copy-padded.
        wtile_safe = jnp.where(do_write, wtile, T)
        p_tag = p_tag.at[wtile_safe, wslot].set(sdst, mode="drop")
        p_val = p_val.at[wtile_safe, wslot].set(inst_val, mode="drop")

        # forwarding set
        if pcfg.write_back:
            fwd_now = bypass                                   # only conflicts bypass
        else:
            fwd_now = upd_hit | miss | bypass                  # write-through
        fdst = jnp.where(fwd_now, sdst, self.Ngd)
        fval = jnp.where(fwd_now, combined, ident)
        # evicted residents (write-back) also forward
        edst = jnp.where(evict, cur_tag, self.Ngd)
        eval_ = jnp.where(evict, cur_pv, ident)

        rdims = (pcfg.region_ny, pcfg.region_nx)
        ncomb = jnp.float32(0.0)
        proxy_src = self.part.global_tile(chip_id,
                                          jnp.minimum(skey // S, T - 1))
        # The whole-P$ flush wave travels with the direct legs only when
        # a non-selective cascade must merge them in one tree walk; in
        # every other mode the flush drain runs in its own lax.cond leg
        # (_flush_drain) so the frequent non-flush supersteps never touch
        # the (T*S,) flush-shaped arrays — on write-back apps those
        # masked no-op legs dominated the superstep.
        split_flush = pcfg.write_back and (
            self._cascade_levels == 0 or pcfg.cascade.selective)

        all_dst = [fdst, edst]
        all_val = [fval, eval_]
        all_src = [proxy_src] * 2
        if pcfg.write_back and not split_flush:
            # non-selective cascade: flush records climb the reduction
            # tree together with the direct legs (they may merge), so
            # they stay in the shared cat, masked on non-flush steps
            def flushed(args):
                p_tag_, p_val_ = args
                ft = p_tag_.reshape(-1)
                fv = p_val_.reshape(-1)
                return ft, fv, jnp.full_like(ft, -1), jnp.full(fv.shape,
                                                               ident)

            def not_flushed(args):
                p_tag_, p_val_ = args
                z = jnp.full((T * S,), -1, jnp.int32)
                return (z, jnp.full((T * S,), ident), p_tag_.reshape(-1),
                        p_val_.reshape(-1))

            ftags, fvals, keep_t, keep_v = jax.lax.cond(
                flush, flushed, not_flushed, (p_tag, p_val))
            p_tag = keep_t.reshape(T, S)
            p_val = keep_v.reshape(T, S)
            all_dst.append(jnp.where(ftags >= 0, ftags, self.Ngd))
            all_val.append(jnp.where(ftags >= 0, fvals, ident))
            all_src.append(jnp.repeat(tile_gids, S))
        cat_dst = jnp.concatenate(all_dst)
        cat_val = jnp.concatenate(all_val)
        cat_src = jnp.concatenate(all_src)
        cat_mask = cat_dst < self.Ngd

        lvl_max = jnp.float32(0.0)
        if self._cascade_levels and not split_flush:
            # Cascaded drain: level-by-level through the region reduction
            # tree instead of straight to the owners (write-through apps
            # cascade their full forward set; non-selective write-back
            # cascades direct legs + flush wave together).
            eligible = jnp.ones(cat_dst.shape[0], bool)
            (mail_val, mail_flag, leg2, owner_leg, per_tile, lvl_max,
             ncomb, off) = self._cascade_drain(
                mail_val, mail_flag, cat_dst, cat_val, cat_src, cat_mask,
                eligible, is_min, chip_id)
        else:
            (mail_val, mail_flag, owner_leg, off_ch, per_tile,
             off) = self._drain_to_owners(
                mail_val, mail_flag, cat_dst, cat_val, cat_mask, cat_src,
                chip_id, rdims, is_min)
            leg2 = netstats.merge_charges(owner_leg, off_ch)

        if split_flush:
            (p_tag, p_val, mail_val, mail_flag, flush_leg, f_owner_leg,
             f_per_tile, f_lvl_max, f_ncomb, f_off) = self._flush_drain(
                flush, p_tag, p_val, mail_val, mail_flag, tile_gids,
                ident, is_min, chip_id, rdims)
            leg2 = netstats.merge_charges(leg2, flush_leg)
            owner_leg = netstats.merge_charges(owner_leg, f_owner_leg)
            per_tile = per_tile + f_per_tile     # same-phase deliveries sum
            lvl_max = jnp.maximum(lvl_max, f_lvl_max)
            ncomb = ncomb + f_ncomb
            if off is not None:
                off = {k: jnp.concatenate([off[k], f_off[k]]) for k in off}

        dmax = jnp.maximum(jnp.max(per_tile), lvl_max)
        charges = dict(netstats.merge_charges(leg1, leg2),
                       owner_msgs=owner_leg["messages"],
                       owner_hop_msgs=owner_leg["hop_msgs"])
        pstats = dict(filtered_at_proxy=jnp.sum(filtered).astype(jnp.float32),
                      coalesced_at_proxy=coalesced.astype(jnp.float32),
                      cascade_combined=ncomb)
        if cfg.telemetry:
            # owner-delivery counts per tile (direct + flush legs summed)
            pstats["tv_delivered"] = per_tile.astype(jnp.float32)
        return mail_val, mail_flag, p_tag, p_val, charges, pstats, dmax, off

    # --------------------------------------------------------- flush drain
    @jax.named_scope("delivery")
    def _flush_drain(self, flush, p_tag, p_val, mail_val, mail_flag,
                     tile_gids, ident, is_min, chip_id, rdims):
        """Write-back whole-P$ spill as its own ``lax.cond`` leg.

        Only actual flush supersteps execute the (T*S,) record drain
        (charge + cascade/deliver + P$ clear); the common non-flush
        superstep takes the no-op branch.  Counter/trace effects are
        identical to draining masked flush arrays every step — a fully
        masked leg charges zero and delivers nothing — so this is pure
        superstep-time savings on write-back apps.  Returns
        (p_tag, p_val, mail_val, mail_flag, merged_leg, owner_leg,
        per_tile, level_max, n_combined, off_records).
        """
        T, S = self.T, self.cfg.proxy.slots
        multi = self.n_chips > 1
        charge_keys = ("messages", "hop_msgs", "intra_die_hops",
                       "inter_die_crossings", "inter_pkg_crossings",
                       "cross_region_msgs")

        def zero_leg(with_off):
            z = {k: jnp.float32(0.0) for k in charge_keys}
            if with_off and multi:
                z["off_chip_msgs"] = jnp.float32(0.0)
                z["off_chip_hop_msgs"] = jnp.float32(0.0)
            return z

        def do_flush(p_tag, p_val, mail_val, mail_flag):
            ft = p_tag.reshape(-1)
            fv = p_val.reshape(-1)
            fmask = ft >= 0
            fdst = jnp.where(fmask, ft, self.Ngd)
            fval = jnp.where(fmask, fv, ident)
            fsrc = jnp.repeat(tile_gids, S)
            cleared_t = jnp.full_like(p_tag, -1)
            cleared_v = jnp.full_like(p_val, ident)
            if self._cascade_levels:
                # selective write-back: the dense flush wave is exactly
                # the record set that profits from the reduction tree
                (mail_val, mail_flag, leg, owner_leg, per_tile, lvl_max,
                 ncomb, off) = self._cascade_drain(
                    mail_val, mail_flag, fdst, fval, fsrc, fmask,
                    jnp.ones_like(fmask), is_min, chip_id)
            else:
                (mail_val, mail_flag, owner_leg, off_ch, per_tile,
                 off) = self._drain_to_owners(
                    mail_val, mail_flag, fdst, fval, fmask, fsrc,
                    chip_id, rdims, is_min)
                leg = netstats.merge_charges(owner_leg, off_ch)
                lvl_max = jnp.float32(0.0)
                ncomb = jnp.float32(0.0)
            return (cleared_t, cleared_v, mail_val, mail_flag, leg,
                    owner_leg, per_tile.astype(jnp.float32), lvl_max,
                    ncomb, off)

        def no_flush(p_tag, p_val, mail_val, mail_flag):
            off = None if self.n_chips == 1 else dict(
                dst=jnp.full((self._flush_off_len(),), self.Ngd,
                             jnp.int32),
                val=jnp.full((self._flush_off_len(),), ident, jnp.float32),
                mask=jnp.zeros((self._flush_off_len(),), bool))
            return (p_tag, p_val, mail_val, mail_flag,
                    zero_leg(with_off=True), zero_leg(with_off=False),
                    jnp.zeros((T,), jnp.float32), jnp.float32(0.0),
                    jnp.float32(0.0), off)

        out = jax.lax.cond(flush, do_flush, no_flush,
                           p_tag, p_val, mail_val, mail_flag)
        return out

    def _flush_off_len(self) -> int:
        """Length of the flush leg's off-chip record buffer: the T*S
        flush wave, replicated per cascade output leg (the direct copy,
        one selective early-exit copy per level, and the tree-root exit —
        matching _cascade_drain's concatenation)."""
        base = self.T * self.cfg.proxy.slots
        if not self._cascade_levels:
            return base
        return base * (2 + self._cascade_levels)

    # ------------------------------------------------------- cascaded drain
    @jax.named_scope("delivery")
    def _cascade_drain(self, mail_val, mail_flag, dst, val, src, mask,
                       eligible, is_min, chip_id):
        """Drain proxy-stage output through the region reduction tree.

        Records climb from their region proxy to the same-index proxy of
        the enclosing super-region at each level, merging with records
        from sibling regions bound for the same destination; only tree
        roots (or selective early exits) forward to the true owner.  Each
        leg is charged exact XY hops; endpoint contention at intermediate
        proxies feeds the BSP time model.  Records with ``eligible=False``
        skip the tree and go straight to their owner.

        Returns (mail_val, mail_flag, merged_charges, owner_leg_charge,
        delivered_per_tile, level_recv_max, n_combined, off_records) —
        ``delivered_per_tile`` is the final owner-delivery count vector
        (summable with other same-superstep delivery legs before the
        max); ``level_recv_max`` the per-proxy receive contention of the
        tree levels.
        """
        cfg, grid = self.cfg, self.cfg.grid
        pcfg = cfg.proxy
        casc = pcfg.cascade
        T = self.T
        rdims = (pcfg.region_ny, pcfg.region_nx)

        cur = jnp.minimum(src, self.Tg - 1)
        alive = mask & eligible
        owner = jnp.minimum(dst // self.Cd, self.Tg - 1)
        legs = []
        out_dst = [dst]
        out_val = [val]
        out_src = [cur]
        out_mask = [mask & ~eligible]
        ncomb = jnp.float32(0.0)
        lvl_max = jnp.float32(0.0)

        for level in range(1, self._cascade_levels + 1):
            rny, rnx = casc.level_dims(pcfg.region_ny, pcfg.region_nx, level)
            if casc.selective:
                # selective exit: once the owner lies inside the record's
                # level-`level` super-region, climbing further cannot merge
                # it with updates from other subtrees on a shorter path —
                # it leaves the tree and goes straight to the owner.
                near = alive & (grid.region_id(cur, rny, rnx)
                                == grid.region_id(owner, rny, rnx))
                out_dst.append(dst)
                out_val.append(val)
                out_src.append(cur)
                out_mask.append(near)
                alive = alive & ~near
            ptile = cascade_proxy_tile(grid, rny, rnx, owner, cur)
            ptile_l = self.part.local_tile(ptile)
            legs.append(netstats.charge(grid, cur, ptile, alive,
                                        region_dims=rdims))
            recv = jax.ops.segment_sum(alive.astype(jnp.float32),
                                       jnp.where(alive, ptile_l, T),
                                       num_segments=T + 1)[:T]
            lvl_max = jnp.maximum(lvl_max, jnp.max(recv))
            cur, dst, val, owner, alive, merged = self._combine_level(
                ptile_l, dst, val, alive, is_min, chip_id)
            ncomb = ncomb + merged

        out_dst.append(dst)
        out_val.append(val)
        out_src.append(cur)
        out_mask.append(alive)
        cat_dst = jnp.concatenate(out_dst)
        cat_val = jnp.concatenate(out_val)
        cat_src = jnp.concatenate(out_src)
        cat_mask = jnp.concatenate(out_mask)
        (mail_val, mail_flag, owner_leg, off_ch, per_tile,
         off) = self._drain_to_owners(
            mail_val, mail_flag, cat_dst, cat_val, cat_mask, cat_src,
            chip_id, rdims, is_min)
        legs.append(owner_leg)
        legs.append(off_ch)
        return (mail_val, mail_flag, netstats.merge_charges(*legs),
                owner_leg, per_tile, lvl_max, ncomb, off)

    def _combine_level(self, ptile_l, dst, val, alive, is_min, chip_id):
        """Merge records that meet at the same (proxy tile, dst) of one
        cascade level into a single combined record (leaders survive).

        Same single-sort lexicographic grouping (``_lex_group``) as the
        P$ batch coalesce; masked records carry sentinel keys and sort to
        the end.  Grouping keys use the window-local proxy tile; the
        surviving records' source tiles are returned as global ids.
        Returns the level's outputs in sorted order plus the merge count.
        """
        T = self.T
        tkey = jnp.where(alive, ptile_l, T)
        dkey = jnp.where(alive, dst, self.Ngd)
        (stile, sdst, salive, (sval,),
         _, leader, gid) = _lex_group(tkey, dkey, T, val,
                                      stable=not is_min)
        agg = self._segment_reduce(sval, salive, gid, is_min)
        nval = agg[gid]
        merged = (jnp.sum(salive) - jnp.sum(leader)).astype(jnp.float32)
        cur = self.part.global_tile(chip_id, jnp.minimum(stile, T - 1))
        owner = jnp.minimum(sdst // self.Cd, self.Tg - 1)
        return cur, sdst, nval, owner, leader, merged

    def _segment_reduce(self, sval, smask, gid, is_min):
        """Combine same-group record values (``gid`` sorted ascending,
        from ``_lex_group``) into one value per group.  The jnp path is
        the oracle; ``backend='pallas'`` routes through the dense
        ``segment_combine`` kernel (masked records become padding)."""
        R = gid.shape[0]
        if self.cfg.backend == "pallas":
            from ..kernels import ops as kops
            # gid ascends over the live records, which precede the masked
            # ones (sentinel keys sort last): already in kernel order
            return kops.segment_combine(jnp.where(smask, gid, -1), sval, R,
                                        combine="min" if is_min else "add",
                                        presorted=True)
        if is_min:
            return jax.ops.segment_min(jnp.where(smask, sval, INF), gid,
                                       num_segments=R,
                                       indices_are_sorted=True)
        return jax.ops.segment_sum(jnp.where(smask, sval, 0.0), gid,
                                   num_segments=R, indices_are_sorted=True)

    # ------------------------------------------------------- chunked stepping
    def _chunk_step_one(self, st, fl):
        """One monolithic superstep on this engine's graph as a (state,
        stats) pair — the scan body unit of the chunked run loop
        (compaction-ladder dispatched, like the per-step path), for
        abstract traces."""
        return self._step_mono(self.graph, st, fl)

    def _chunk_impl(self, graph, state, flush, done, steps_left, *,
                    length: int):
        """Scan ``length`` monolithic supersteps in one device dispatch
        (see :func:`_scan_steps` for the carry/termination contract)."""
        write_back = self.cfg.proxy is not None and self.cfg.proxy.write_back
        return _scan_steps(
            lambda st, fl: self._step_mono(graph, st, fl), state, flush,
            done, steps_left, length, write_back)

    # ----------------------------------------------------------------- run
    def run(self, state, max_supersteps: Optional[int] = None,
            progress_every: int = 0, chunk: Optional[int] = None,
            observer=None):
        """Run supersteps until drained; returns (state, RunResult).

        ``chunk`` overrides ``EngineConfig.run_chunk``: supersteps per
        device dispatch.  ``chunk=0`` selects the legacy per-step loop
        (one host sync per superstep — the benchmark baseline); any K>=1
        scans K supersteps per dispatch with identical results.
        ``progress_every`` reports at chunk granularity: the first chunk
        boundary at or past each multiple prints the true executed
        superstep count.

        ``observer`` (obs.timeline.Observer) receives ``on_run_start``
        with the run's :class:`~repro.obs.timeline.RunMeta`, one
        ``on_chunk`` span per chunk (per superstep on the legacy loop) at
        the existing host-accounting boundary, and ``on_run_end`` with
        the RunResult.  Attaching one adds no host syncs and leaves
        counters/trace/final state bit-identical."""
        with HostSpan("engine.run_start"):
            self._require_mono("run")
            cfg = self.cfg
            maxs = max_supersteps or cfg.max_supersteps
            K = cfg.run_chunk if chunk is None else int(chunk)
            counters = TrafficCounters()
            trace = SuperstepTrace(double_buffer=cfg.double_buffer)
            cycles = 0.0
            steps = 0
            pkg = cfg.pkg
            links = link_provisioning(cfg.grid, pkg)
            fill = links["diameter"] * 0.5                 # pipeline fill
            values_before = state["values"] if cfg.sanitize else None
            if observer is not None:
                observer.on_run_start(RunMeta(
                    app=self.app.name, grid_ny=cfg.grid.ny,
                    grid_nx=cfg.grid.nx, chunk=K, backend=cfg.backend,
                    sanitize=cfg.sanitize, telemetry=cfg.telemetry,
                    pkg=pkg, grid=cfg.grid))
            if K > 0:
                progress = _ProgressReporter(self.app.name, progress_every,
                                             sanitize=cfg.sanitize,
                                             tiles=self.T)
                if self._stat_names is None:   # one abstract trace per engine
                    self._stat_names = _stat_keys(
                        self._chunk_step_one, state,
                        jnp.zeros((), jnp.bool_))
                chunk_fn = functools.partial(self._chunk, self.graph,
                                             length=K)

        def account(stats):
            """Legacy-loop per-superstep accounting.  The chunked branch
            uses the vectorized twin (chunk_counters / append_chunk /
            add_chunk_cycles below) — edit BOTH in lockstep; the
            bit-identity tests in tests/test_chunked.py are the gate."""
            nonlocal cycles
            _sanitize_gate(cfg, self.app.name,
                           float(stats.get("sanity_violations", 0.0)))
            counters.add(superstep_counters(stats))
            trace.append_step(stats, element_bits=cfg.element_bits)
            # ---- BSP time model for this superstep ----------------------
            step_cycles = superstep_cycles(stats, pkg, links)
            if step_cycles > 0 or stats["pending"] > 0:
                cycles += step_cycles + fill

        def add_chunk_cycles(stacked, n_act, cycles):
            # vectorized BSP terms, accumulated in execution order —
            # bit-identical to account() per step
            if cfg.sanitize:
                bad = stacked.get("sanity_violations")
                if bad is not None:
                    _sanitize_gate(cfg, self.app.name,
                                   float(np.sum(bad[:n_act])))
            sc = chunk_cycles(stacked, n_act, pkg, links)
            pend = np.asarray(stacked["pending"][:n_act])
            for s, p in zip(sc.tolist(), pend.tolist()):
                if s > 0 or p > 0:
                    cycles += s + fill
            return cycles

        if K <= 0:
            state, steps = self._run_legacy(state, maxs, progress_every,
                                            account, observer=observer)
        else:
            state, steps, cycles = _drain_chunked(
                chunk_fn, state, maxs, self._stat_names, counters, trace,
                cfg.element_bits, progress, add_chunk_cycles, cycles,
                observer=observer)
        with HostSpan("engine.finish"):
            counters.supersteps = steps
            time_s = cycles / (CLOCK_GHZ * 1e9)
            result = RunResult(counters=counters, cycles=cycles,
                               time_s=time_s, supersteps=steps, trace=trace)
            if cfg.sanitize:
                from ..analysis import invariants as _inv
                write_back = (cfg.proxy is not None
                              and cfg.proxy.write_back)
                findings = _inv.check_run(
                    result, pkg=pkg, grid=cfg.grid,
                    where=f"sanitize/{self.app.name}",
                    write_back=write_back, seeds=self._n_seeds,
                    combine=self.app.combine,
                    values_before=values_before,
                    values_after=state["values"], drained=steps < maxs)
                _inv.assert_clean(findings,
                                  context=f"run({self.app.name})")
            if observer is not None:
                observer.on_run_end(result)
        return state, result

    def _run_legacy(self, state, maxs, progress_every, account,
                    observer=None):
        """The seed per-step loop: one dispatch + one host sync per
        superstep.  Kept as the measured baseline for the chunked loop
        (``benchmarks/engine_throughput.py``) and its bit-identity tests.
        With an ``observer``, each superstep emits one single-step
        :class:`~repro.obs.timeline.ChunkSpan` at the per-step host sync
        this loop already pays."""
        cfg = self.cfg
        write_back = cfg.proxy is not None and cfg.proxy.write_back
        sync_ctr = default_registry().counter("engine.host_syncs")
        steps = 0
        flush_flag = jnp.asarray(False)
        while steps < maxs:
            at = dict(chunk=steps, step=steps)
            with HostSpan("engine.dispatch", **at) as t_dispatch:
                state, stats = self._superstep(self.graph, state,
                                               flush_flag)
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
            if stats["pending"] == 0:
                # live work drained; spill any write-back P$ residue (the
                # paper's TSU heuristic: flush when queues/buffers go idle).
                # Repeated flushes terminate: a spilled value that does not
                # improve its owner generates no new work.
                if write_back and stats["p_resident"] > 0:
                    flush_flag = jnp.asarray(True)
                    continue
                break
            if progress_every and steps % progress_every == 0:
                print(f"  [{self.app.name}] step {steps} pending={stats['pending']:.0f}")
        return state, steps


@dataclasses.dataclass
class RunResult:
    counters: TrafficCounters
    cycles: float
    time_s: float
    supersteps: int
    # per-superstep level-traffic record: what makes the run re-priceable
    # under other package configs (costmodel.price(per_superstep_peak=...))
    trace: Optional[SuperstepTrace] = None


def _sanitize_gate(cfg, app_name: str, violations: float) -> None:
    """Raise on a nonzero on-device ``sanity_violations`` count (the
    ``EngineConfig.sanitize`` per-superstep checks computed in ``_step``).
    Shared by the legacy per-step and chunked accounting paths of both
    run loops."""
    if cfg.sanitize and violations > 0:
        from ..analysis.invariants import SanitizerError
        raise SanitizerError(
            f"sanitizer: {violations:.0f} on-device invariant violation(s) "
            f"during {app_name} (monotone relaxation / mailbox consistency "
            f"/ NaN checks in the superstep body)")


def superstep_counters(stats) -> TrafficCounters:
    """One superstep's measured traffic as a TrafficCounters delta.
    Shared by the monolithic and distributed run loops so the two paths
    cannot drift in which fields they accumulate."""
    return TrafficCounters(
        messages=stats["messages"], hop_msgs=stats["hop_msgs"],
        owner_msgs=stats["owner_msgs"],
        owner_hop_msgs=stats["owner_hop_msgs"],
        intra_die_hops=stats["intra_die_hops"],
        inter_die_crossings=stats["inter_die_crossings"],
        inter_pkg_crossings=stats["inter_pkg_crossings"],
        filtered_at_proxy=stats["filtered_at_proxy"],
        coalesced_at_proxy=stats["coalesced_at_proxy"],
        cascade_combined=stats.get("cascade_combined", 0.0),
        cross_region_msgs=stats.get("cross_region_msgs", 0.0),
        off_chip_msgs=stats.get("off_chip_msgs", 0.0),
        off_chip_hop_msgs=stats.get("off_chip_hop_msgs", 0.0),
        edges_processed=stats["edges_processed"],
        records_consumed=stats["records_consumed"],
        reactivations=stats.get("reactivations", 0.0),
        stale_edges=stats.get("stale_edges", 0.0), supersteps=1)


def superstep_cycles(stats, pkg, links: dict) -> float:
    """BSP cycles of one superstep: max over (tile compute, per-level
    network serialization, endpoint contention).  The distributed runtime
    maxes the board-level leg on top of this.  (Thin wrapper around
    ``costmodel.step_cycles`` so the run loops and analytic re-pricing
    cannot drift; ``link_provisioning`` also lives in costmodel now.)"""
    bits = MSG_BITS
    return float(step_cycles(
        pkg, links,
        compute_ops=float(stats["compute_per_tile_max"]),
        intra_bits=float(stats["intra_die_hops"]) * bits,
        die_bits=float(stats["inter_die_crossings"]) * bits,
        pkg_bits=float(stats["inter_pkg_crossings"]) * bits,
        endpoint_bits=float(stats["delivered_max_per_tile"]) * bits))


def chunk_counters(stacked, n_active: int) -> TrafficCounters:
    """One chunk's accumulated traffic as a TrafficCounters delta.

    The chunked-loop rendering of :func:`superstep_counters`: one numpy
    reduction per field per chunk instead of a python accumulation per
    superstep (per-step host accounting would eat the chunked loop's
    dispatch savings).  Bit-identical to per-step accumulation because
    every counter is an integer-valued count: float64 sums of integers
    below 2**53 are exact under any association.
    """
    n = int(n_active)

    def tot(key):
        a = stacked.get(key)
        if a is None:
            return 0.0
        return float(np.sum(np.asarray(a[:n], dtype=np.float64)))

    return TrafficCounters(
        messages=tot("messages"), hop_msgs=tot("hop_msgs"),
        owner_msgs=tot("owner_msgs"),
        owner_hop_msgs=tot("owner_hop_msgs"),
        intra_die_hops=tot("intra_die_hops"),
        inter_die_crossings=tot("inter_die_crossings"),
        inter_pkg_crossings=tot("inter_pkg_crossings"),
        filtered_at_proxy=tot("filtered_at_proxy"),
        coalesced_at_proxy=tot("coalesced_at_proxy"),
        cascade_combined=tot("cascade_combined"),
        cross_region_msgs=tot("cross_region_msgs"),
        off_chip_msgs=tot("off_chip_msgs"),
        off_chip_hop_msgs=tot("off_chip_hop_msgs"),
        edges_processed=tot("edges_processed"),
        records_consumed=tot("records_consumed"),
        reactivations=tot("reactivations"), stale_edges=tot("stale_edges"),
        supersteps=n)


def chunk_cycles(stacked, n_active: int, pkg, links: dict) -> np.ndarray:
    """Vectorized :func:`superstep_cycles` over a chunk's stacked stats:
    one ``costmodel.step_cycles`` call on ``(n_active,)`` float64 vectors
    (elementwise identical to the per-step scalar calls)."""
    n = int(n_active)
    bits = MSG_BITS

    def vec(key):
        return np.asarray(stacked[key][:n], dtype=np.float64)

    return np.atleast_1d(step_cycles(
        pkg, links,
        compute_ops=vec("compute_per_tile_max"),
        intra_bits=vec("intra_die_hops") * bits,
        die_bits=vec("inter_die_crossings") * bits,
        pkg_bits=vec("inter_pkg_crossings") * bits,
        endpoint_bits=vec("delivered_max_per_tile") * bits))


# int32 per-superstep stats that can exceed f32's exact-integer range at
# paper-scale runs; _scan_steps carries them on an exact int32 side
# channel next to the packed f32 rows (order matters — the scan body's
# drained test reads index 0, so "pending" must stay first; see
# packed_step).  "p_resident" joined after the repro.analysis jaxpr
# linter's int-stat-f32-row rule flagged it: write-back P$ residency is
# bounded by T*slots, which passes 2**24 at the paper's million-PU scale.
# "reactivations" and "stale_edges" (cursor restarts, see _churn) are
# int32 counts that grow like edges_processed.
_EXACT_INT_STATS = ("pending", "edges_processed", "records_consumed",
                    "p_resident", "reactivations", "stale_edges")


def _churn_args(stats) -> dict:
    """The ``engine.account`` span's arguments beyond its place: the
    chunk's ``reactivations`` and ``stale_edges``, summed over ``stats``'
    rows (a chunk's masked rows hold 0)."""
    return {k: int(np.sum(stats[k])) for k in ("reactivations",
                                                 "stale_edges")}


def _stat_keys(step_one, state, flush):
    """Scalar stat names of ``step_one``'s stats dict in the packed-vector
    order ``_scan_steps`` emits (sorted, with ``active`` appended), via an
    abstract trace — no device computation.  Telemetry *vector* stats
    (``tv_*`` / ``pc_*``, nonzero ndim) are excluded: they ride the
    scan's separate stacked-dict channel under their own names, so the
    packed f32 row layout is identical with telemetry on or off."""
    stats_shape = jax.eval_shape(step_one, state, flush)[1]
    return sorted(k for k, v in stats_shape.items()
                  if v.ndim == 0) + ["active"]


def _drain_chunked(chunk_fn, state, maxs, keys, counters, trace,
                   element_bits, progress, add_chunk_cycles, cycles,
                   observer=None, *, steps0=0, flush0=None, boundary=None,
                   vec_sums=None):
    """The host side of the chunked run loop, shared verbatim by the
    monolithic and distributed engines (so chunk unpacking, accounting
    and termination cannot drift between them).

    Per chunk: one device dispatch (``chunk_fn``), one host sync, then
    vectorized accounting — ``chunk_counters`` into ``counters``,
    ``SuperstepTrace.append_chunk`` into ``trace``, and the caller's
    ``add_chunk_cycles(stacked, n_act, cycles) -> cycles`` closure for
    the BSP time model (it accumulates sequentially, preserving the
    legacy loop's float-addition order).  Returns (state, steps, cycles).

    ``observer`` (obs.timeline.Observer) is called once per chunk at the
    *existing* host-accounting boundary with the already-fetched arrays
    plus wall-clock span times — attaching one adds zero host syncs and
    cannot perturb the computation (it only reads).  Every chunk's
    device_get increments the ``engine.host_syncs`` metric, observer or
    not, so telemetry-on/off sync counts are directly comparable.

    The keyword-only extensions serve the distributed engine's
    fault-tolerance layer (defaults keep the monolithic call untouched):
    ``steps0`` / ``flush0`` resume the loop from a restored checkpoint
    carry; ``boundary(steps, state, flush, host_done, cycles) -> cycles``
    runs at each chunk host-accounting boundary *after* the chunk's
    accounting (it checkpoints on cadence and may raise the fault
    injector's chip-loss error, which the caller's retry loop turns into
    a rollback); ``vec_sums`` (a dict) accumulates the per-superstep sum
    of every telemetry vector stat (``pc_*``) across the run — the
    straggler-rebalancing load feed, riding the existing fetch.
    """
    sync_ctr = default_registry().counter("engine.host_syncs")
    steps = int(steps0)
    chunk_idx = 0
    flush = jnp.zeros((), jnp.bool_) if flush0 is None else \
        jnp.asarray(flush0, jnp.bool_)
    done = jnp.zeros((), jnp.bool_)
    while steps < maxs:
        at = dict(chunk=chunk_idx, step=steps)
        with HostSpan("engine.dispatch", **at) as t_dispatch:
            (state, flush, done, _), (packed, ints, vecs) = chunk_fn(
                state, flush, done, jnp.int32(maxs - steps))
        with HostSpan("engine.fetch", **at) as t_fetch:
            # the single host sync of this chunk:
            host_done, packed, ints, vecs = jax.device_get(
                (done, packed, ints, vecs))
            sync_ctr.inc()
        with HostSpan("engine.account", **at,
                      **_churn_args(dict(zip(_EXACT_INT_STATS, ints.T)))
                      ) as t_account:
            stacked = {k: packed[:, i] for i, k in enumerate(keys)}
            for i, k in enumerate(_EXACT_INT_STATS):
                stacked[k] = ints[:, i]      # exact int32, not the f32 row
            n_act = int(np.sum(stacked["active"]))
            if n_act:
                counters.add(chunk_counters(stacked, n_act))
                trace.append_chunk(stacked, n_act, element_bits=element_bits)
                cycles = add_chunk_cycles(stacked, n_act, cycles)
                if vec_sums is not None:
                    for k, v in vecs.items():
                        s = np.sum(np.asarray(v[:n_act], np.float64), axis=0)
                        vec_sums[k] = vec_sums.get(k, 0.0) + s
        if observer is not None:
            observer.on_chunk(ChunkSpan(
                index=chunk_idx, step_lo=steps, step_hi=steps + n_act,
                t_dispatch=t_dispatch.t, t_fetch=t_fetch.t,
                t_account=t_account.t,
                stats={k: np.asarray(v[:n_act]) for k, v in stacked.items()},
                vecs={k: np.asarray(v[:n_act]) for k, v in vecs.items()}))
        steps += n_act
        chunk_idx += 1
        progress.report(steps, stacked, n_act)
        if boundary is not None:
            with HostSpan("engine.boundary", **at):
                cycles = boundary(steps, state, flush, bool(host_done),
                                  cycles)
        if host_done or n_act == 0:
            break
    return state, steps, cycles


def _legacy_span(steps, stats, t_dispatch, t_fetch, t_account):
    """One per-step-loop superstep as a single-step ChunkSpan: scalar
    stats become ``(1,)`` arrays and telemetry vectors (``tv_*`` /
    ``pc_*``) become ``(1, W)`` rows — the same shapes the chunked loop
    emits, so observers need not care which loop ran."""
    scal, vecs = {}, {}
    for k, v in stats.items():
        a = np.asarray(v)
        if a.ndim == 0:
            scal[k] = a[None]
        else:
            vecs[k] = a[None]
    scal["active"] = np.ones((1,), np.float32)
    return ChunkSpan(index=steps - 1, step_lo=steps - 1, step_hi=steps,
                     t_dispatch=t_dispatch, t_fetch=t_fetch,
                     t_account=t_account, stats=scal, vecs=vecs)


def _scan_steps(step_one, state, flush, done, steps_left, length: int,
                write_back: bool):
    """Scan ``length`` supersteps in one device dispatch.

    ``step_one(state, flush) -> (new_state, stats)`` is one engine
    superstep (monolithic, or a whole distributed superstep including
    the boundary exchange).  The carry holds the engine state, the
    write-back flush flag, the drained flag and the remaining superstep
    budget — all on device.  Each iteration applies the same post-step
    rules the legacy host loop applied between dispatches: a just-drained
    engine with write-back P$ residue schedules a flush superstep; a
    drained engine without residue stops.  Iterations past the stop point
    (or past the budget) skip the superstep entirely (``lax.cond``) and
    emit a zeroed row with ``active=0``.  Shared by the monolithic and
    distributed chunked run loops so the two cannot drift in
    flush/termination semantics.

    The per-step stats are packed into ONE ``(n_stats,)`` f32 vector (in
    :func:`_stat_keys` order) so the scan stacks a single ``(length,
    n_stats)`` buffer instead of one buffer per stat — a large share of
    the per-iteration overhead at small grid sizes.  The int32 stats
    that can outgrow f32's 2**24 integer range at paper-scale runs
    (see ``_EXACT_INT_STATS``) additionally ride an exact int32 side
    channel; every other stat is f32 on device already or a count far
    below 2**24, so the packing loses nothing.  The flush/termination
    decisions read the exact pre-packing integers.

    Returns ((state, flush, done, steps_left), (stacked, stacked_ints,
    stacked_vecs)) with shapes ``(length, n_stats)`` f32,
    ``(length, len(_EXACT_INT_STATS))`` int32, and — telemetry only — a
    dict of ``(length, W)`` f32 vector stats (empty dict otherwise, so
    the non-telemetry compiled program is unchanged).
    """
    stats_shape = jax.eval_shape(step_one, state, flush)[1]
    keys = sorted(k for k, v in stats_shape.items() if v.ndim == 0)
    vkeys = sorted(k for k, v in stats_shape.items() if v.ndim > 0)

    def packed_step(st, fl):
        new_state, stats = step_one(st, fl)
        vec = jnp.stack([stats[k].astype(jnp.float32) for k in keys])
        ints = jnp.stack([stats[k].astype(jnp.int32)
                          for k in _EXACT_INT_STATS])
        vstats = {k: stats[k].astype(jnp.float32) for k in vkeys}
        return (new_state, vec, ints,
                stats["p_resident"] if write_back else jnp.int32(0),
                vstats)

    def idle_step(st, _fl):
        # pending=1 so a masked idle row can never read as "drained";
        # the row is discarded anyway (active=0)
        vstats = {k: jnp.zeros(stats_shape[k].shape, jnp.float32)
                  for k in vkeys}
        return (st, jnp.zeros((len(keys),), jnp.float32),
                jnp.array([1] + [0] * (len(_EXACT_INT_STATS) - 1),
                          jnp.int32), jnp.int32(0), vstats)

    def body(carry, _):
        state, flush, done, left = carry
        active = jnp.logical_and(~done, left > 0)
        # cond, not select: iterations past the stop point skip the
        # superstep entirely instead of computing and discarding it
        new_state, vec, ints, p_res, vstats = jax.lax.cond(
            active, packed_step, idle_step, state, flush)
        drained = active & (ints[0] == 0)
        if write_back:
            flush_next = drained & (p_res > 0)
        else:
            flush_next = jnp.zeros((), jnp.bool_)
        done_next = done | (drained & ~flush_next)
        row = jnp.concatenate([vec, active.astype(jnp.float32)[None]])
        return (new_state, flush_next, done_next,
                left - active.astype(left.dtype)), (row, ints, vstats)

    return jax.lax.scan(body, (state, flush, done, steps_left), None,
                        length=length)


def _lex_group(key, sub, key_end, *vals, stable: bool = True):
    """Single-sort lexicographic (key, sub) record grouping.

    One fused ``lax.sort`` with ``num_keys=2`` orders records by the
    (key, sub) composite — the sort the two-stable-argsort idiom
    (argsort by sub, then by key) and a packed ``(key << k) | sub``
    key both express, but with one sort pass, no gathers, and no int64
    requirement — carrying ``vals`` along as passengers.  Masked records
    must hold the sentinel key ``key_end``, above every live key, so
    they sort last and the live mask is recovered from the sorted keys.
    With ``stable`` ties in (key, sub) keep arrival order, so downstream
    f32 segment sums accumulate in the same order as the two-argsort
    formulation: bit-identical results.  Min combines are
    order-independent and pass ``stable=False``: the TPU compiles an
    unstable sort about twice as fast.

    Returns (skey, ssub, smask, svals, new_key, new_pair, gid):
      new_key:  sorted-order mask of the first live record of each key;
      new_pair: first live record of each (key, sub) group — the group
                leaders; gid numbers the groups (masked rows -> last id).
    """
    R = key.shape[0]
    skey, ssub, *svals = jax.lax.sort(
        (key, sub) + tuple(vals), num_keys=2, is_stable=stable)
    smask = skey < key_end
    first = jnp.arange(R) == 0
    new_key = smask & (first | (skey != jnp.roll(skey, 1)))
    new_pair = smask & (new_key | (ssub != jnp.roll(ssub, 1)))
    gid = jnp.cumsum(new_pair.astype(jnp.int32)) - 1
    gid = jnp.where(smask, gid, R - 1)
    return skey, ssub, smask, tuple(svals), new_key, new_pair, gid


class _ProgressReporter:
    """Chunk-granularity progress for the scanned run loops: reports the
    true executed superstep count at the first chunk boundary at or past
    each ``every`` multiple (the per-step loop's ``steps % every == 0``
    would silently skip multiples that fall inside a chunk).

    Progress flows through the obs metrics registry — gauges
    ``progress.<app>.steps`` / ``.pending`` updated every chunk, counter
    ``progress.<app>.reports`` per printed line — so harnesses read it
    without scraping stdout; when the sanitizer is on, the line also
    carries the cumulative ``sanity_violations`` count.

    Compacted runs (``EngineConfig.compaction > 1``) additionally feed
    the ``engine.active_fraction`` gauge (mean active-tile fraction of
    the latest chunk) and per-capacity ``engine.bucket_occupancy.<cap>``
    counters (supersteps spent in each ladder rung) from the
    ``active_tiles`` / ``bucket_cap`` telemetry stats the bucket switch
    emits — they ride the same chunk stat fetch, zero extra syncs."""

    def __init__(self, name: str, every: int, sanitize: bool = False,
                 tiles: int = 0):
        self.name = name
        self.every = every
        self.sanitize = sanitize
        self.tiles = tiles
        self._next = every
        self._violations = 0.0
        reg = default_registry()
        self._g_steps = reg.gauge(f"progress.{name}.steps")
        self._g_pending = reg.gauge(f"progress.{name}.pending")
        self._c_reports = reg.counter(f"progress.{name}.reports")
        self._g_active = reg.gauge("engine.active_fraction")
        self._bucket_counters: dict = {}

    def report(self, steps: int, stacked, n_act: int) -> None:
        if n_act == 0:
            return
        pending = float(stacked["pending"][n_act - 1])
        self._g_steps.set(steps)
        self._g_pending.set(pending)
        act = stacked.get("active_tiles")
        if act is not None and self.tiles:
            self._g_active.set(
                float(np.mean(act[:n_act])) / self.tiles)
            caps, cnts = np.unique(
                np.asarray(stacked["bucket_cap"][:n_act]),
                return_counts=True)
            for cap, cnt in zip(caps.tolist(), cnts.tolist()):
                c = self._bucket_counters.get(int(cap))
                if c is None:
                    c = default_registry().counter(
                        f"engine.bucket_occupancy.{int(cap)}")
                    self._bucket_counters[int(cap)] = c
                c.inc(float(cnt))
        if self.sanitize and "sanity_violations" in stacked:
            self._violations += float(
                np.sum(stacked["sanity_violations"][:n_act]))
        if not self.every or steps < self._next:
            return
        self._c_reports.inc()
        line = (f"  [{self.name}] step {steps} (chunk of {n_act}) "
                f"pending={pending:.0f}")
        if self.sanitize:
            line += f" sanity_violations={self._violations:.0f}"
        print(line)
        while self._next <= steps:
            self._next += self.every


@jax.named_scope("delivery")
def _deliver(mail_val, mail_flag, dst, val, mask, owner, T, Nd, is_min,
             backend: str = "jnp"):
    """Combine records into owner mailboxes; returns the (T,) per-tile
    delivered-record counts (endpoint contention before the max).

    Two scatters instead of the seed's three: one combines the arriving
    values per mailbox index, one counts arrivals per index — and the
    count vector then yields both the flag update (``count > 0`` ==
    scatter-max of the mask) and the per-tile endpoint contention
    (mailbox indices of one tile are contiguous, so per-tile delivered
    records are a reshape-sum of the counts).  XLA CPU serializes
    scatters per update row, so every scatter removed is the single
    biggest superstep saving; counts are integers, so the derived values
    are bit-identical to the scatter-max/segment-sum formulation.  min
    combines are order-independent (bitwise identical to the seed); add
    combines apply ``mail + sum(arrivals)`` instead of the seed's
    sequential scatter order — equal up to f32 re-association.
    """
    if backend == "pallas":
        return _deliver_pallas(mail_val, mail_flag, dst, val, mask, owner,
                               T, Nd, is_min)
    # masked records point one past the end; mode="drop" discards them at
    # the scatter itself — no padded copy of the mailbox per superstep
    safe_dst = jnp.where(mask, dst, Nd)
    cnt = jnp.zeros((Nd,), jnp.int32).at[safe_dst].add(
        mask.astype(jnp.int32), mode="drop")
    if is_min:
        inc = jnp.full((Nd,), INF).at[safe_dst].min(
            jnp.where(mask, val, INF), mode="drop")
        mv = jnp.minimum(mail_val, inc)
    else:
        inc = jnp.zeros((Nd,), jnp.float32).at[safe_dst].add(
            jnp.where(mask, val, 0.0), mode="drop")
        mv = mail_val + inc
    mf = mail_flag | (cnt > 0)
    per_tile = jnp.sum(cnt.reshape(T, Nd // T), axis=1)
    return mv, mf, per_tile.astype(jnp.float32)


@jax.named_scope("delivery")
def _deliver_pallas(mail_val, mail_flag, dst, val, mask, owner, T, Nd,
                    is_min):
    """Pallas rendering of the owner delivery in one launch
    (``kernels.deliver_fused``): the records are sorted by destination,
    and a work list of (mailbox block, record block) pairs drives a grid
    over (8, 128) blocks, so each mailbox block folds in only the record
    blocks that hold its records.  It returns the relaxed mailbox (min:
    == scatter-min, bitwise; add: accumulate — equal to the jnp oracle
    up to f32 re-association) and the per-index arrival counts.  Flags
    and per-tile endpoint contention derive from the counts exactly like
    the jnp path (counts are integers, mailbox indices of one tile are
    contiguous)."""
    from ..kernels import ops as kops
    comb = "min" if is_min else "add"
    seg = jnp.where(mask, dst, -1)                 # negative = padding
    mv, cnt = kops.deliver_fused(seg, val, mail_val, combine=comb)
    mf = mail_flag | (cnt > 0)
    per_tile = jnp.sum(cnt.reshape(T, Nd // T), axis=1)
    return mv, mf, per_tile


def _emit_lanes(capped, take_v2d, cur_lo2, cur_val2, B: int):
    """The OQ emit's lane -> source-slot map, shared by both fronts.

    Row t's B emission lanes stream its slots' remaining edges in slot
    order: ``capped`` (rows, Cs) is the inclusive prefix of the slots'
    remainders capped at B (non-decreasing along each row), ``take_v2d``
    each slot's share of it, ``cur_lo2``/``cur_val2`` the (rows, Cs)
    cursor views.  Lane b reads slot ``s = min(#{c : capped[t, c] <= b},
    Cs - 1)`` (``searchsorted(capped[t], b, side='right')``, clamped), so
    a lane past the row's total reads the last slot, masked out.

    No search and no gather: ``c <= s`` holds exactly where the slot
    before c is spent by lane b (``capped[t, c-1] <= b``), so ``x[t, s]``
    is the sum of x's steps ``x[t, c] - x[t, c-1]`` over those c — one
    dense compare over the row's Cs slots per lane, fused with its
    reductions (no (rows, B, Cs) buffer).  int32 sums wrap exactly, and
    the value rides as its int32 bits, so every lane equals a searchsorted
    lookup and gathers bit for bit (inf, NaN and -0.0 included).

    Returns (pos, lane_val, emit_mask), each (rows, B): the lane's edge
    position (unclipped), its slot's cursor value, and b < row total."""
    b_idx = jnp.arange(B, dtype=jnp.int32)
    capped_prev = capped - take_v2d                 # capped[t, c-1]; 0 at c=0
    upto = capped_prev[:, None, :] <= b_idx[:, None]    # (rows, B, Cs)

    def at_slot(x):
        steps = jnp.diff(x, axis=1, prepend=0)
        return jnp.sum(jnp.where(upto, steps[:, None, :], 0), axis=2,
                       dtype=jnp.int32)

    pos = b_idx + at_slot(cur_lo2 - capped_prev)
    bits = at_slot(jax.lax.bitcast_convert_type(cur_val2, jnp.int32))
    lane_val = jax.lax.bitcast_convert_type(bits, jnp.float32)
    emit_mask = b_idx < capped[:, -1:]
    return pos, lane_val, emit_mask


# the churn of an app that never restarts a cursor
_NO_CHURN = (jnp.int32(0), jnp.int32(0))


def _churn(restart, vals, ident, cur_lo, row_lo):
    """``(reactivations, stale_edges)`` of one superstep's cursor
    restarts, shared by both fronts: ``restart`` marks the items whose
    cursor an improvement restarts, ``vals`` their values and ``cur_lo``
    their cursors before it.  A reactivation is a restart of an item
    already reached (its value was not the combine identity); its stale
    edges are those it had streamed under the old value, ``cur_lo -
    row_lo``.  A cursor that never started (0, below ``row_lo``) streamed
    none.  Exact int32 sums: the dense and compacted fronts give the same
    counts, since every lane a window drops restarts nothing."""
    again = restart & (vals != ident)
    stale = jnp.where(again, jnp.maximum(cur_lo - row_lo, 0), 0)
    return (jnp.sum(again, dtype=jnp.int32),
            jnp.sum(stale, dtype=jnp.int32))


def capacity_ladder(T: int, levels: int) -> tuple:
    """Window-capacity ladder for active-set compaction: ``(T, T/4,
    T/16, ...)`` — the dense window plus ``levels`` power-of-two rungs
    (each a quarter of the previous, floored at 1 tile; rungs that no
    longer shrink are dropped).  Descending, so ``bucket_index`` can
    pick the smallest capacity that fits the active count."""
    caps = [int(T)]
    for k in range(1, max(int(levels), 0) + 1):
        c = max(int(T) >> (2 * k), 1)
        if c < caps[-1]:
            caps.append(c)
    return tuple(caps)


def bucket_index(n_act, caps: tuple):
    """Index of the smallest ladder capacity that holds ``n_act`` active
    tiles (0 = the dense window; traced — ``n_act`` may be a device
    scalar, so this is the on-device ``lax.switch`` selector)."""
    idx = jnp.int32(0)
    for j, c in enumerate(caps[1:], start=1):
        idx = jnp.where(n_act <= c, jnp.int32(j), idx)
    return idx


def _compact_window(active, W: int, T: int):
    """Stable compaction of the (T,) active mask into a W-slot window.

    Returns (w_valid, w_rows, rows_drop): per-window-slot validity, the
    source tile row each slot gathers (invalid slots clamp to T-1 — the
    caller must mask their gathered work to zero), and the scatter-back
    row index (invalid slots -> sentinel row T, for ``mode="drop"``).
    The cumsum keeps active tiles in tile order, which is what makes
    the compacted record stream order-identical to the dense one.
    The slot->tile map is a searchsorted over the inclusive cumsum (the
    j-th active tile is the first row where the cumsum reaches j+1), NOT
    a T-row scatter and NOT an argsort: XLA CPU serializes indexed
    scatters and gathers per row, so a T-row scatter here (~70us at
    T=1024) costs more than the whole windowed front saves, and a
    full-length stable sort is worse still.  The W-row scatter-backs the
    callers do are fine — their row count shrinks with the bucket."""
    csum = jnp.cumsum(active.astype(jnp.int32))
    tile_map = jnp.searchsorted(
        csum, jnp.arange(1, W + 1, dtype=jnp.int32),
        side="left").astype(jnp.int32)
    w_valid = tile_map < T
    w_rows = jnp.minimum(tile_map, T - 1)
    rows_drop = jnp.where(w_valid, w_rows, T)
    return w_valid, w_rows, rows_drop


def _pad(a: np.ndarray, n: int, fill) -> np.ndarray:
    a = np.asarray(a)
    if a.shape[0] == n:
        return a
    out = np.full((n,), fill, dtype=a.dtype)
    out[: a.shape[0]] = a
    return out
