"""A whole run, at a test size on the CPU, with the timed path broken
underneath: ``correct`` has to come out false for each fault a cell can
have, and true without one."""

import dataclasses
import time

import jax
import numpy as np
import pytest

from bench.run import run_cell
from bench.tests.helpers import tiny_cell

CELLS = ["pagerank-s19", "bfs-s19", "serve-c16-s17"]


def _alter(values):
    v = np.array(values, copy=True)
    v[np.flatnonzero(np.isfinite(v))[0]] += 1.0
    return v


def state_unchanged(mp):
    """Every update step returns the state it was given."""
    from repro.core import apps
    from repro.serve import sweep

    get = apps.get_program

    def frozen(name, **kw):
        return dataclasses.replace(get(name, **kw),
                                   apply=lambda acc, old, meta, v0=0: old)

    mp.setattr(apps, "get_program", frozen)
    mp.setattr(sweep.LaneTable, "apply_rows", lambda *a, **k: None)


def half_left_out(mp):
    """Half of each batch of work is dropped: every other shard of a sweep,
    every other lane of a fused group."""
    from repro.core import executor
    from repro.serve import sweep

    run = executor.PerShardExecutor.run
    mp.setattr(executor.PerShardExecutor, "run",
               lambda self, loaded, *a: (r for r in run(self, loaded, *a)
                                         if r.shard_id % 2 == 0))
    apply_rows = sweep.LaneTable.apply_rows
    mp.setattr(sweep.LaneTable, "apply_rows",
               lambda self, acc, slots, *a: apply_rows(
                   self, acc[::2], slots[::2], *a))


def answer_altered(mp):
    """One value of each answer is changed where the answer is produced."""
    from repro.core.vsw import VSWEngine
    from repro.serve import sweep

    run = VSWEngine.run

    def altered_run(self, *a, **k):
        r = run(self, *a, **k)
        r.values = _alter(r.values)
        return r

    mp.setattr(VSWEngine, "run", altered_run)
    retire = sweep.LaneTable.retire

    def altered_retire(self, emit):
        return retire(self, lambda res: emit(
            dataclasses.replace(res, values=_alter(res.values))))

    mp.setattr(sweep.LaneTable, "retire", altered_retire)


def one_program_raises(mp):
    """Every PPR query raises instead of answering; the others answer."""
    from repro.serve import service

    get = service.get_lane_program

    def failing(program, **kw):
        if program == "ppr":
            raise RuntimeError("planted fault")
        return get(program, **kw)

    mp.setattr(service, "get_lane_program", failing)


def _run(cell):
    return run_cell(tiny_cell(cell), 4294967311, 0.5, False,
                    t_start=time.perf_counter(), devices=jax.devices())


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [state_unchanged, half_left_out,
                                   answer_altered], ids=lambda f: f.__name__)
def test_fault_makes_run_incorrect(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = _run(cell)
    assert out["correct"] is False, out["checks"]


def test_failed_queries_make_serving_run_incorrect(monkeypatch):
    one_program_raises(monkeypatch)
    out = _run("serve-c16-s17")
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["failed_queries"]["value"] > 0
    assert out["checks"]["level_mismatch"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["checks"] and all(
        {"value", "limit"} == set(v) for v in out["checks"].values())
