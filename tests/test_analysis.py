"""repro.analysis: each pass must *detect its hazard class*, not just run.

Every pass gets a mutation test — introduce the hazard (a host callback
in a traced step, an aliased overwrite window, a dropped delivery, a
corrupted counter/trace, a dead module) and require the finding; remove
it and require silence.  Plus the regression tests for the real findings
the passes surfaced on this tree (``p_resident`` riding the f32 stat row
uncovered — rule ``int-stat-f32-row``), and the ``EngineConfig.sanitize``
contract: bit-identical results, and a raised ``SanitizerError`` on a
corrupted engine state.
"""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import deadcode, invariants, jaxprlint, pallas_races
from repro.analysis.findings import Finding, Report, load_baseline
from repro.core import engine as eng_mod
from repro.core.costmodel import DCRA_SRAM
from repro.core.netstats import MSG_BITS, SuperstepTrace, TrafficCounters
from repro.core.tilegrid import square_grid
from repro.graph import apps, rmat_edges

GRID = square_grid(16)


@pytest.fixture(scope="module")
def g():
    return rmat_edges(6, edge_factor=4, seed=3)


@pytest.fixture(scope="module")
def root(g):
    return int(np.argmax(g.out_degree()))


@pytest.fixture(scope="module")
def bfs_res(g, root):
    return apps.bfs(g, root, GRID, oq_cap=16)


def _rules(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------------- jaxprlint
class TestJaxprLint:
    def test_host_sync_mutation(self):
        def clean(x):
            return jnp.sum(x * 2)

        def dirty(x):
            jax.debug.print("x={x}", x=x)
            return jnp.sum(x)

        x = jnp.ones((4,))
        assert jaxprlint.lint_step_fn(clean, (x,), "t") == []
        fs = jaxprlint.lint_step_fn(dirty, (x,), "t")
        assert "host-sync" in _rules(fs)

    def test_host_sync_inside_scan_body(self):
        # the walker must recurse into scan bodies — that is where the
        # chunked run loop would hide a per-iteration host round trip
        def dirty(x):
            def body(c, _):
                jax.debug.print("c={c}", c=c)
                return c + 1, c
            return jax.lax.scan(body, x, None, length=3)

        fs = jaxprlint.lint_step_fn(dirty, (jnp.float32(0),), "t")
        assert "host-sync" in _rules(fs)

    def test_scatter_mode_mutation(self):
        idx = jnp.array([0, 1, 1], jnp.int32)
        v = jnp.ones((3,))

        def drop(x):
            return x.at[idx].set(v, mode="drop")

        def clip(x):
            return x.at[idx].set(v, mode="clip")

        def clip_add(x):          # commutative: safe under duplicates
            return x.at[idx].add(v, mode="clip")

        x = jnp.zeros((4,))
        assert jaxprlint.lint_step_fn(drop, (x,), "t") == []
        assert jaxprlint.lint_step_fn(clip_add, (x,), "t") == []
        fs = jaxprlint.lint_step_fn(clip, (x,), "t")
        assert "scatter-mode" in _rules(fs)

    def test_engine_steps_are_clean(self, g, root):
        eng, state, _ = apps.engine_and_state("bfs", g, GRID, root=root,
                                              oq_cap=16)
        fs = jaxprlint.lint_step_fn(eng._chunk_step_one,
                                    (state, jnp.zeros((), jnp.bool_)), "t")
        assert fs == []

    def test_int_stat_regression_p_resident(self, g, root):
        # the real finding this pass surfaced: 'p_resident' (int32,
        # bounded by T*slots — past 2**24 at million-PU scale) rode the
        # packed f32 stat row uncovered.  It is covered now; removing it
        # from the side channel must re-fire the rule.
        assert "p_resident" in eng_mod._EXACT_INT_STATS
        # the scan body's drained test reads int row 0: order is load-bearing
        assert eng_mod._EXACT_INT_STATS[0] == "pending"
        eng, state, _ = apps.engine_and_state("bfs", g, GRID, root=root,
                                              oq_cap=16)
        shapes = jaxprlint.stats_shapes_of(eng._chunk_step_one, state,
                                           jnp.zeros((), jnp.bool_))
        assert jaxprlint.lint_int_stats(shapes, eng_mod._EXACT_INT_STATS,
                                        "t") == []
        uncovered = [k for k in eng_mod._EXACT_INT_STATS
                     if k != "p_resident"]
        fs = jaxprlint.lint_int_stats(shapes, uncovered, "t")
        assert any(f.rule == "int-stat-f32-row"
                   and f.where.endswith("p_resident") for f in fs)

    def test_backend_drift_mutation(self):
        a = {"x": jax.ShapeDtypeStruct((4,), jnp.float32)}
        assert jaxprlint.lint_backend_drift(a, dict(a), "t") == []
        b = {"x": jax.ShapeDtypeStruct((4,), jnp.int32)}
        fs = jaxprlint.lint_backend_drift(a, b, "t")
        assert _rules(fs) == ["backend-dtype-drift"]
        fs = jaxprlint.lint_backend_drift(a, {}, "t")
        assert _rules(fs) == ["backend-dtype-drift"]


# ---------------------------------------------------------- pallas_races
class _Spec:
    def __init__(self, block_shape, index_map):
        self.block_shape = block_shape
        self.index_map = index_map


def _call(index_map, grid=(4,), block=(8,)):
    return pallas_races.CapturedCall(
        kernel_name="k", grid=grid, out_specs=[_Spec(block, index_map)],
        out_shapes=[(8,)])


class TestPallasRaces:
    def test_aliased_overwrite_mutation(self):
        aliased = _call(lambda i: 0)          # every program, one window
        fs = pallas_races.check_call(aliased, "overwrite", "t")
        assert "aliased-overwrite" in _rules(fs)
        # same geometry under a commutative combine: the standard
        # revisit-accumulate reduction pattern — clean
        assert pallas_races.check_call(aliased, "add", "t") == []
        # disjoint windows: clean under any combine
        disjoint = _call(lambda i: i)
        assert pallas_races.check_call(disjoint, "overwrite", "t") == []

    @pytest.mark.parametrize("index_map,grid,block,tables,combine,want", [
        # one contiguous run per window (the revisit-accumulate idiom)
        (lambda i: i // 2, (4,), (4,), None, "add", []),
        # window 0 revisited after window 1 ran: stale write-back
        (lambda i: i % 2, (4,), (4,), None, "add", ["split-revisit"]),
        # the second half of the output is never written
        (lambda i: 0, (2,), (4,), None, "add", ["unwritten-window"]),
        # work-list grids are judged on the tables the call was given
        (lambda i, t: t[i], (4,), (4,), (np.array([0, 0, 1, 1]),), "add",
         []),
        (lambda i, t: t[i], (4,), (4,), (np.array([0, 1, 0, 1]),), "add",
         ["split-revisit"]),
        (lambda i, t: t[i], (4,), (4,), (np.array([1, 1, 1, 1]),), "add",
         ["unwritten-window"]),
    ])
    def test_revisit_and_coverage_rules(self, index_map, grid, block,
                                        tables, combine, want):
        call = _call(index_map, grid=grid, block=block)
        if tables is not None:
            call.n_prefetch, call.prefetch = len(tables), tables
        assert _rules(pallas_races.check_call(call, combine, "t")) == want

    def test_untraceable_prefetch_is_reported(self):
        call = _call(lambda i, t: t[i])
        call.n_prefetch = 1                   # tables never captured
        fs = pallas_races.check_call(call, "add", "t")
        assert _rules(fs) == ["prefetch-unknown"]

    def test_no_pallas_call_is_vacuous(self):
        fs = pallas_races.check_fn(lambda: None, "add", "t")
        assert _rules(fs) == ["no-pallas-call"]

    def test_kernel_suite_only_documented_exception(self):
        # the repo's kernels must prove disjoint (or commutative-aliased)
        # — except decode_attention's online-softmax carry, whose output
        # window is deliberately revisited across KV blocks and is safe
        # only because the Pallas grid executes sequentially.  That one
        # lives in the committed baseline.
        keys = {f.key for f in pallas_races.check_kernels()}
        assert keys == {"pallas_races:aliased-overwrite:"
                        "kernels/decode_attention:_kernel[out0]"}


# ------------------------------------------------------------ invariants
def _counters(**over):
    base = dict(messages=10.0, hop_msgs=12.0, owner_msgs=8.0,
                owner_hop_msgs=10.0, intra_die_hops=6.0,
                inter_die_crossings=4.0, inter_pkg_crossings=2.0,
                filtered_at_proxy=1.0, coalesced_at_proxy=1.0,
                cascade_combined=0.0, edges_processed=10.0,
                records_consumed=8.0, supersteps=3)
    base.update(over)
    return TrafficCounters(**base)


class TestInvariants:
    def test_clean_counters(self):
        assert invariants.check_counters(_counters(), where="t") == []

    def test_dropped_delivery_breaks_conservation(self):
        fs = invariants.check_counters(_counters(owner_msgs=7.0), where="t")
        assert "owner-conservation" in _rules(fs)
        # write-back P$ absorbs without a counter: <= is allowed there...
        fs = invariants.check_counters(
            _counters(owner_msgs=7.0, records_consumed=7.0), where="t",
            write_back=True)
        assert fs == []
        # ...but over-delivery is a bug in either mode
        fs = invariants.check_counters(_counters(owner_msgs=11.0,
                                                 owner_hop_msgs=13.0),
                                       where="t", write_back=True)
        assert "owner-conservation" in _rules(fs)

    def test_corrupted_counter(self):
        fs = invariants.check_counters(_counters(messages=-1.0), where="t")
        assert "counter-negative" in _rules(fs)
        fs = invariants.check_counters(_counters(edges_processed=10.5),
                                       where="t")
        assert "counter-nonint" in _rules(fs)
        fs = invariants.check_counters(_counters(intra_die_hops=7.0),
                                       where="t")
        assert "hop-decomposition" in _rules(fs)
        fs = invariants.check_counters(_counters(records_consumed=9.0),
                                       where="t")
        assert "consumed-bound" in _rules(fs)
        assert invariants.check_counters(_counters(records_consumed=9.0),
                                         where="t", seeds=1) == []

    def _trace(self):
        tr = SuperstepTrace()
        for pend in (3.0, 0.0):
            tr.append_step(dict(compute_per_tile_max=2.0, intra_die_hops=3,
                                inter_die_crossings=1,
                                inter_pkg_crossings=0,
                                delivered_max_per_tile=2,
                                edges_processed=4, records_consumed=2,
                                pending=pend))
        return tr

    def test_trace_mutations(self):
        assert invariants.check_trace(self._trace(), where="t") == []
        tr = self._trace()
        tr.pending[-1] = 5.0
        assert "trace-not-drained" in _rules(
            invariants.check_trace(tr, where="t"))
        # an undrained final step is fine when the budget was declared
        assert invariants.check_trace(tr, where="t", drained=False) == []
        tr = self._trace()
        tr.intra_bits[0] += 1.0
        assert "trace-bit-quantum" in _rules(
            invariants.check_trace(tr, where="t"))
        tr = self._trace()
        tr.die_bits[0] = -float(MSG_BITS)
        assert "trace-negative" in _rules(
            invariants.check_trace(tr, where="t"))
        tr = self._trace()
        tr.pending.append(0.0)
        assert "trace-length" in _rules(
            invariants.check_trace(tr, where="t"))

    def test_monotone_frontier_mutation(self):
        assert invariants.check_values([2.0, 3.0], [1.0, 3.0], "min",
                                       where="t") == []
        fs = invariants.check_values([2.0, 3.0], [2.0, 4.0], "min",
                                     where="t")
        assert _rules(fs) == ["monotone-frontier"]
        # add-combine apps accumulate: growth is not a violation
        assert invariants.check_values([2.0], [4.0], "add", where="t") == []

    def test_reprice_mutation(self, bfs_res):
        run = bfs_res.run
        assert invariants.check_reprice(run, DCRA_SRAM, GRID,
                                        where="t") == []
        bad = copy.deepcopy(run)
        bad.trace.compute_ops[0] += 1e6
        fs = invariants.check_reprice(bad, DCRA_SRAM, GRID, where="t")
        assert _rules(fs) == ["reprice-ratio"]

    def test_check_run_composes_clean(self, bfs_res):
        fs = invariants.check_run(bfs_res.run, pkg=DCRA_SRAM, grid=GRID,
                                  where="t", seeds=1)
        assert fs == []

    def test_assert_clean_raises(self):
        invariants.assert_clean([])
        with pytest.raises(invariants.SanitizerError):
            invariants.assert_clean(
                [Finding("invariants", "counter-negative", "t", "boom")])


# -------------------------------------------------------------- sanitize
class TestSanitize:
    def test_bit_identical_fast(self, g, root):
        r0 = apps.bfs(g, root, GRID, oq_cap=16)
        r1 = apps.bfs(g, root, GRID, oq_cap=16, sanitize=True)
        assert np.array_equal(r0.values, r1.values)
        assert r0.run.cycles == r1.run.cycles
        assert r0.run.counters.as_dict() == r1.run.counters.as_dict()

    @pytest.mark.slow
    def test_bit_identical_all_apps(self, g, root):
        # the acceptance contract: sanitize=True runs every app
        # bit-identically to sanitize=False (checks observe, never branch)
        bins = max(g.n_rows // 8, 1)
        from repro.graph.rmat import histogram_input
        hv = histogram_input(g, bins)
        x = np.random.default_rng(5).random(g.n_cols).astype(np.float32)

        def runs(**kw):
            pr = apps.table2_proxy(GRID, "pagerank")
            sp = apps.table2_proxy(GRID, "spmv", cascade_levels=1)
            hp = apps.table2_proxy(GRID, "histo")
            wp = apps.table2_proxy(GRID, "wcc")
            return [
                apps.bfs(g, root, GRID, oq_cap=16, **kw),
                apps.sssp(g, root, GRID,
                          proxy=apps.table2_proxy(GRID, "sssp"),
                          oq_cap=16, **kw),
                apps.wcc(g, GRID, proxy=wp, oq_cap=16, **kw),
                apps.pagerank(g, GRID, proxy=pr, epochs=2, oq_cap=16, **kw),
                apps.spmv(g, x, GRID, proxy=sp, oq_cap=16, **kw),
                apps.histogram(hv, bins, GRID, proxy=hp, oq_cap=8, **kw),
            ]

        for r0, r1 in zip(runs(), runs(sanitize=True)):
            assert np.array_equal(r0.values, r1.values)
            assert r0.run.cycles == r1.run.cycles
            assert r0.run.counters.as_dict() == r1.run.counters.as_dict()

    @pytest.mark.parametrize("chunk", [0, 8])
    def test_corrupted_state_raises(self, g, root, chunk):
        # a NaN planted in the value array is unrepairable (min-combine
        # comparisons against NaN are False, so it survives every step):
        # the on-device check must count it and the run loop must raise —
        # through both the legacy and the chunked accounting paths
        eng, state, _ = apps.engine_and_state("bfs", g, GRID, root=root,
                                              oq_cap=16, sanitize=True)
        victim = (root + 1) % g.n_rows
        state["values"] = state["values"].at[victim].set(jnp.nan)
        with pytest.raises(invariants.SanitizerError):
            eng.run(state, chunk=chunk)

    def test_distributed_sanitize_runs(self, g, root):
        r0 = apps.bfs(g, root, GRID, oq_cap=16, chips=4)
        r1 = apps.bfs(g, root, GRID, oq_cap=16, chips=4, sanitize=True)
        assert np.array_equal(r0.values, r1.values)
        assert r0.run.cycles == r1.run.cycles


# -------------------------------------------------------------- deadcode
class TestDeadcode:
    def test_dead_and_quarantined(self, tmp_path):
        src = tmp_path / "src" / "pkg"
        src.mkdir(parents=True)
        (src / "__init__.py").write_text("")
        (src / "used.py").write_text("X = 1\n")
        (src / "dead.py").write_text("Y = 2\n")
        (src / "quar.py").write_text(
            f"{deadcode.MARKER} — kept for reference\nZ = 3\n")
        t = tmp_path / "tests"
        t.mkdir()
        (t / "test_x.py").write_text("from pkg import used\n")
        fs, meta = deadcode.check_repo(tmp_path)
        assert meta["dead"] == ["pkg.dead"]
        assert meta["quarantined"] == ["pkg.quar"]
        assert _rules(fs) == ["dead-module"]

    def test_repo_has_no_unmarked_dead_modules(self):
        import pathlib
        repo = pathlib.Path(__file__).resolve().parent.parent
        fs, meta = deadcode.check_repo(repo)
        assert fs == [], meta["dead"]


# ------------------------------------------------------ findings/baseline
class TestFindings:
    def test_report_round_trip(self):
        rep = Report(passes=["jaxprlint"], matrix=["bfs/jnp/mono"])
        rep.extend([Finding("jaxprlint", "host-sync", "bfs/jnp/mono",
                            "msg")])
        back = Report.from_json(rep.to_json())
        assert back.keys() == rep.keys()
        assert back.matrix == rep.matrix

    def test_baseline_gate(self, tmp_path):
        f1 = Finding("p", "r", "w1", "m")
        f2 = Finding("p", "r", "w2", "different message, same site kind")
        rep = Report(findings=[f1, f2])
        path = tmp_path / "base.json"
        path.write_text(Report(findings=[f1]).baseline_json())
        base = load_baseline(path)
        assert [f.key for f in rep.new_vs_baseline(base)] == [f2.key]
        # message changes do not churn the key
        f1b = Finding("p", "r", "w1", "reworded")
        assert Report(findings=[f1b]).new_vs_baseline(base) == []
        assert load_baseline(tmp_path / "missing.json") == []


# ----------------------------------------------------------------- runner
@pytest.mark.slow
def test_runner_static_cell_clean():
    import pathlib
    from repro.analysis import runner
    repo = pathlib.Path(__file__).resolve().parent.parent
    rep = runner.run_all(repo, app_names=["bfs"], passes=["jaxprlint"])
    assert rep.findings == []
    assert "bfs/jnp/mono" in rep.matrix
