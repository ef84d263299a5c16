"""``correct`` has to come out false when the timed path is wrong. At tiny
widths on the CPU, past the harness's look for a chip: the control of each
cell (the step below its stated precision) and each fault the cell can
have, planted in the program underneath the harness, must fail the
comparison with the reference at the cell's own limits."""
from __future__ import annotations

import io
import pathlib
import sys

import numpy as np
import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
from harness import bench, check, spec, system, traffic  # noqa: E402

system.import_program()  # the program under test, from the checkout's src/

WORKLOADS = [w["name"] for w in spec.benchmark()["workloads"]]
SEED = 2 ** 32 + 5


@pytest.mark.parametrize("name", WORKLOADS)
def test_control_is_not_correct(name):
    st = bench.setup(name, SEED, tiny=True, require_chip=False)
    ctl = st.cell.config["control"]
    st.prog.call = lambda hz, params, **kw: control.control_results(
        st, hz, ctl)
    win = bench.window(st, 0.5, keep_rng=traffic.seed_rng(SEED, 7))
    nums, limits = bench.run_checks(st, win)
    assert not check.verdict(nums, limits), (nums, limits)


def _altered_answer(sim):
    real = sim.simulate_fleet

    def broken(*a, **k):
        r = real(*a, **k)
        rows, cols = np.nonzero(r.active if r.active is not None
                                else np.ones(r.splits.shape, bool))
        i, j = rows[len(rows) // 2], cols[len(cols) // 2]
        r.splits = np.array(r.splits)
        r.splits[i, j] = 17 if r.splits[i, j] != 17 else 10
        return r
    return [(sim, "simulate_fleet", broken)]


def _half_batch(sim):
    real = sim.engine.estimate_fleet

    def broken(episode, *a, **k):
        est = real(episode, *a, **k)
        half = est.shape[0] // 2
        est[half:] = est[:half].mean()
        return est
    return [(sim.engine, "estimate_fleet", broken)]


def _controller_state_unchanged(sim):
    real = sim.engine.controller_step

    def broken(table, state, tp, *, cfg):
        _, split = real(table, state, tp, cfg=cfg)
        return state, split
    return [(sim.engine, "controller_step", broken),
            (sim.pool, "controller_step", broken)]


def _scheduler_state_unchanged(sim):
    real = sim.pool.scheduler_step

    def broken(cfg, n_cells, state, *a, **k):
        _, share = real(cfg, n_cells, state, *a, **k)
        return state, share
    return [(sim.pool, "scheduler_step", broken)]


def _table_lookup_off_by_one(sim):
    import jax.numpy as jnp
    real = sim.engine.controller_step

    def broken(table, state, tp, *, cfg):  # bucket b reads bucket b - 1
        return real(jnp.roll(table, 1, axis=-1), state, tp, cfg=cfg)
    return [(sim.engine, "controller_step", broken),
            (sim.pool, "controller_step", broken)]


FAULTS = {"altered_answer": _altered_answer, "half_batch": _half_batch,
          "controller_state_unchanged": _controller_state_unchanged,
          "scheduler_state_unchanged": _scheduler_state_unchanged,
          "table_lookup_off_by_one": _table_lookup_off_by_one}


def _cases():
    for name in WORKLOADS:
        cell = spec.cell(name)
        for fault in FAULTS:
            if (fault == "scheduler_state_unchanged"
                    and not cell.mix.get("scheduler")):
                continue  # no scheduler in the cell
            if fault == "table_lookup_off_by_one" and cell.mix["kind"] != "pool":
                continue  # 24 tiny decisions cross none of the table's steps
            yield name, fault


@pytest.mark.parametrize("name,fault", list(_cases()))
def test_fault_is_not_correct(name, fault, monkeypatch):
    import repro.sim as sim
    import repro.sim.engine
    import repro.sim.pool

    def clear():
        sim.engine._sweep_fn.cache_clear()
        sim.pool.pool_programs.cache_clear()

    clear()
    for mod, attr, fn in FAULTS[fault](sim):
        monkeypatch.setattr(mod, attr, fn)
    try:
        out = bench.run(name, SEED, 0.5, False, tiny=True,
                        require_chip=False, log=io.StringIO())
    finally:
        monkeypatch.undo()
        clear()
    assert out["correct"] is False, out["checks"]
