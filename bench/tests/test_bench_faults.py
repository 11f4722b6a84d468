"""A run with the timed path broken underneath comes out not correct:
once for each fault the BFS cells can have.  Each drives the harness
past its look for chips, at a tiny size on the CPU; the four-package
cell runs its four chips on one device, exchange included."""
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib as tl
from repro.core.engine import DataLocalEngine
from repro.distrib import driver
from repro.distrib.driver import DistributedEngine


def _state_unchanged(monkeypatch):
    """Every superstep returns the state it was given, and says done."""
    mono, dist = DataLocalEngine._step_mono, DistributedEngine._raw_step

    def step_mono(self, graph, state, flush):
        _, stats = mono(self, graph, state, flush)
        return state, dict(stats, pending=stats["pending"] * 0)

    def raw_step(self, mesh, double_buffer=False):
        step = dist(self, mesh, double_buffer)

        def unchanged(graph, state, chip_ids, flush):
            _, agg = step(graph, state, chip_ids, flush)
            return state, dict(agg, pending=agg["pending"] * 0)
        return unchanged

    monkeypatch.setattr(DataLocalEngine, "_step_mono", step_mono)
    monkeypatch.setattr(DistributedEngine, "_raw_step", raw_step)


def _half_dropped(monkeypatch):
    """Half of each superstep's records (those of odd tiles) left out."""
    front = DataLocalEngine._front_dense

    def half(self, graph, state, tile_gids):
        out = list(front(self, graph, state, tile_gids))
        out[12] = out[12] & (out[13] % 2 == 0)      # emit_mask, src_tile
        return tuple(out)

    monkeypatch.setattr(DataLocalEngine, "_front_dense", half)


def _exchange_dropped(monkeypatch):
    """Records bound for another chip never arrive."""
    def drop(mail_val, mail_flag, flat, mask, val, seg, n_seg, is_min):
        return mail_val, mail_flag, jnp.zeros((n_seg,), jnp.float32)

    monkeypatch.setattr(driver, "_combine_into_mail", drop)


def _answer_altered(monkeypatch):
    """One reached vertex's level is one more than the program found."""
    for cls in (DataLocalEngine, DistributedEngine):
        def run(self, state, _orig=cls.run, **kw):
            out, res = _orig(self, state, **kw)
            values = np.array(out["values"])
            values[np.flatnonzero(np.isfinite(values))[-1]] += 1
            return dict(out, values=values), res
        monkeypatch.setattr(cls, "run", run)


FAULTS = {"state_unchanged": _state_unchanged, "half_dropped": _half_dropped,
          "exchange_dropped": _exchange_dropped,
          "answer_altered": _answer_altered}
CASES = [(tl.CELL_1, f) for f in FAULTS if f != "exchange_dropped"] + \
        [(tl.CELL_4, f) for f in FAULTS]


@pytest.mark.parametrize("cell", [tl.CELL_1, tl.CELL_4])
def test_sound_run_is_correct(cell):
    res = tl.run_tiny(cell)
    assert res["correct"] and res["attempted"] >= 1
    assert res["checks"]["level_mismatches"]["value"] == 0


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    res = tl.run_tiny(cell)
    assert res["attempted"] >= 1
    assert not res["correct"]
    assert res["checks"]["level_mismatches"]["value"] > 0
