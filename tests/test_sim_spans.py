"""Host spans of the fleet path under a profiler session: on the windowed
fleet, the slot pool with and without PF, and the mesh serving path,
every stage span appears, nested as documented (``ingest`` inside the
stage whose inputs it copies, the readback inside ``estimator_fwd`` /
``controller``); each ``ingest`` span's ``bytes`` stat is the ``nbytes``
of what the shapes say it copied; and results are bit-identical with the
session open and without one."""
import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.channel import scenarios as sc
from repro.channel.scenarios import WINDOW
from repro.core.controller import ControllerConfig
from repro.core.pso import LookupTable
from repro.estimator.model import EstimatorConfig, init_estimator
from repro.estimator.ssm import SSMConfig, init_ssm
from repro.models.vgg import FULL, vgg_split_profile
from repro.sim import (SchedulerConfig, estimate_fleet, make_serving_mesh,
                       simulate_fleet)
from repro.sim import telemetry as tel

CALL = "test_call"  # the test's own span around one call
STAGES = {"featurize", "ingest", "estimator_fwd", "controller", "gather",
          "split_metrics"}
CFG = ControllerConfig(ewma_alpha=0.5, hysteresis_steps=2, fallback_split=3)
N_SC = 16
F32 = 4  # bytes of one float32 / int32


@pytest.fixture(scope="module")
def prof():
    return vgg_split_profile(FULL)


@pytest.fixture(scope="module")
def table(prof):
    return LookupTable(ue_name="t", table=np.full(41, 3, np.int32),
                       tp_min_mbps=np.zeros(len(prof.data_bytes)),
                       feasible_prefilter=np.ones(len(prof.data_bytes),
                                                  bool))


def _lstm():
    e = EstimatorConfig(n_sc=N_SC, lstm_hidden=8, hidden=8)
    return e, init_estimator(e, jax.random.PRNGKey(0))


def _ssm():
    c = SSMConfig(n_heads=2, head_dim=4, state_dim=4, hidden=8)
    return c, init_ssm(c, jax.random.PRNGKey(0))


def _fleet(n=8, t=6):
    rng = np.random.default_rng(5)
    scen = np.asarray(sc.SCENARIOS)[np.arange(n) % len(sc.SCENARIOS)]
    return sc.gen_episode_batch(scen, t, rng, n_sc=N_SC)


def _churn():
    rng = np.random.default_rng(7)
    schedule = sc.make_churn_schedule(
        sc.ChurnConfig(arrival_rate=4.0, mean_dwell=5.0), 12, rng)
    scen = np.asarray(sc.SCENARIOS, object)[
        np.arange(schedule.n_sessions) % len(sc.SCENARIOS)]
    sessions = sc.gen_episode_batch(scen, schedule.max_dwell, rng, n_sc=N_SC)
    return schedule, sessions


def _setup(kind):
    """(call, stage ingest bytes the shapes give, top-level stage order)."""
    if kind == "fleet":
        ep = _fleet()
        n, t = ep.n_ues, ep.n_steps
        fwd = n * t * (WINDOW * 15 + 2 * N_SC * 14 + 1) * F32
        ctl = (n * 41 + 1 + n * t) * F32  # tables, warm split, estimates
        return ((lambda p, tb: simulate_fleet(ep, tb, p, CFG,
                                              estimator=_lstm())),
                {"estimator_fwd": fwd, "controller": ctl},
                ["featurize", "estimator_fwd", "controller",
                 "split_metrics"])
    schedule, sessions = _churn()
    m, el = sessions.n_ues, sessions.n_steps
    kw = dict(churn=schedule, capacity=16, estimator=_ssm())
    if kind == "pool_pf":
        kw.update(sched=SchedulerConfig(policy="pf"), n_cells=2,
                  cell_idx=(np.arange(m) % 2).astype(np.int32))
    feats = m * sessions.kpms.shape[1] * 16 * F32
    # shared table row, warm split, true tp, cell, dwell, arrival, est,
    # ready_end
    ctl = (41 + 1 + m * el + 3 * m + m * el + schedule.horizon) * F32
    return ((lambda p, tb: simulate_fleet(sessions, tb, p, CFG, **kw)),
            {"estimator_fwd": feats, "controller": ctl},
            ["featurize", "estimator_fwd", "controller", "gather",
             "split_metrics"])


def _traced(tmp_path, fn):
    """fn()'s result and its spans as (name, parent, stats) in start
    order, from a profiler session around one call."""
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(CALL):
            out = fn()
    pb = max(tmp_path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    events = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                got = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name in STAGES | {CALL}]
                if any(n == CALL for n, *_ in got):
                    events = got
    tree, stack = [], []
    for n, s, e, st in sorted(events, key=lambda x: (x[1], x[1] - x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            assert e <= stack[-1][2], f"{n} overlaps {stack[-1][0]}"
        tree.append((n, stack[-1][0] if stack else None, st))
        stack.append((n, s, e))
    return out, [x for x in tree if x[0] != CALL]


def _same(a, b):
    for f in ("splits", "true_tp", "est_tp", "delay_s", "privacy",
              "energy_j", "active", "prb_share"):
        x, y = getattr(a, f), getattr(b, f)
        if x is None:
            assert y is None, f
        else:
            np.testing.assert_array_equal(x, y, err_msg=f)


KINDS = ["fleet", "pool", "pool_pf"]


@pytest.mark.parametrize("kind", KINDS)
def test_stage_spans_nest(kind, prof, table, tmp_path):
    call, _, order = _setup(kind)
    _, tree = _traced(tmp_path, lambda: call(prof, table))
    top = [n for n, parent, _ in tree if parent == CALL]
    # one featurize, estimator chunks, then each later stage once
    assert [n for i, n in enumerate(top) if i == 0 or n != top[i - 1]] \
        == order
    assert top.count("controller") == 1
    parents = {(n, parent) for n, parent, _ in tree if parent != CALL}
    assert parents == {("ingest", "estimator_fwd"), ("ingest", "controller")}
    for stage in ("estimator_fwd", "controller"):  # one copy-in per call
        assert sum(1 for n, p, _ in tree if p == stage) \
            == top.count(stage)


@pytest.mark.parametrize("kind", KINDS)
def test_ingest_bytes_match_shapes(kind, prof, table, tmp_path):
    call, want, _ = _setup(kind)
    _, tree = _traced(tmp_path, lambda: call(prof, table))
    got = {}
    for n, parent, st in tree:
        if n == "ingest":
            got[parent] = got.get(parent, 0) + st["bytes"]
        else:
            assert "bytes" not in st
    assert got == want


@pytest.mark.parametrize("kind", KINDS)
def test_results_identical_under_profiler(kind, prof, table, tmp_path):
    call, _, _ = _setup(kind)
    base = call(prof, table)
    traced, _ = _traced(tmp_path, lambda: call(prof, table))
    _same(base, traced)


def test_ingest_counts_what_it_copies(tmp_path):
    x = np.arange(12, dtype=np.float64).reshape(3, 4)
    _, tree = _traced(tmp_path, lambda: _ingest_two(x))
    assert tree == [("ingest", CALL, {"bytes": 12 * 4 + 3 * 4})]


def _ingest_two(x):
    with tel.ingest() as put:
        a = put(x)  # float64 on the host, float32 on the device
        b = put(x[:, 0], np.int32)
    assert a.dtype == np.float32 and b.dtype == np.int32
    return a, b


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs 2 devices")
@pytest.mark.parametrize("est", ["lstm", "ssm"])
def test_mesh_path_spans_and_names(est, tmp_path):
    from repro.sim.serving import serving_program, ssm_serving_program
    ep = _fleet(n=4, t=3)
    mesh = make_serving_mesh("2x1")
    pair = _lstm() if est == "lstm" else _ssm()
    prog = (serving_program(pair[0], mesh) if est == "lstm"
            else ssm_serving_program(pair[0], mesh))
    assert prog.__name__ == ("fwd_sharded" if est == "lstm"
                             else "ssm_step_sharded")
    base = estimate_fleet(ep, pair, serving=mesh)
    got, tree = _traced(tmp_path,
                        lambda: estimate_fleet(ep, pair, serving=mesh))
    np.testing.assert_array_equal(base, got)
    assert [n for n, p, _ in tree if p == CALL][0] == "featurize"
    assert {(n, p) for n, p, _ in tree if p != CALL} \
        == {("ingest", "estimator_fwd")}
    assert all(st["bytes"] > 0 for n, _, st in tree if n == "ingest")
