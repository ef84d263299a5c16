"""Telemetry-layer tests: telemetry-on bit-identity against the default
programs across the plain / scheduled / churn / online paths, masked
metric invariants (inactive slots contribute nothing, histogram totals
equal the active-sample count, the event ring never overflows silently),
decode/export round trips, and the stage timer. Property tests
run through hypothesis when available, otherwise a fixed-seed sweep of
the same checks (the suite's standard pattern)."""
try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - depends on environment
    HAVE_HYPOTHESIS = False

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel import scenarios as sc
from repro.core.controller import ControllerConfig
from repro.core.pso import LookupTable
from repro.estimator.model import EstimatorConfig, init_estimator
from repro.models.vgg import FULL, vgg_split_profile
from repro.sim import (DriftConfig, OnlineConfig, SchedulerConfig,
                       TelemetryConfig, TelemetryRecord, simulate_fleet,
                       timed, to_jsonl, to_prometheus)
from repro.sim import telemetry as tel

F32 = jnp.float32
I32 = jnp.int32


@pytest.fixture(scope="module")
def prof():
    return vgg_split_profile(FULL)


@pytest.fixture(scope="module")
def table(prof):
    return LookupTable(ue_name="t", table=np.full(41, 3, np.int32),
                       tp_min_mbps=np.zeros(len(prof.data_bytes)),
                       feasible_prefilter=np.ones(len(prof.data_bytes),
                                                  bool))


CFG = ControllerConfig(ewma_alpha=0.5, hysteresis_steps=2, fallback_split=3)


def _episode(n, T=6, seed=5, **kw):
    rng = np.random.default_rng(seed)
    scen = np.asarray(sc.SCENARIOS)[np.arange(n) % len(sc.SCENARIOS)]
    return sc.gen_episode_batch(scen, T, rng, n_sc=16, **kw)


def _churn(T=12, seed=7, rate=4.0, dwell=5.0):
    rng = np.random.default_rng(seed)
    schedule = sc.make_churn_schedule(
        sc.ChurnConfig(arrival_rate=rate, mean_dwell=dwell), T, rng)
    scen = np.asarray(sc.SCENARIOS, object)[
        np.arange(schedule.n_sessions) % len(sc.SCENARIOS)]
    sessions = sc.gen_episode_batch(scen, schedule.max_dwell, rng, n_sc=16)
    return schedule, sessions


def _tiny_estimator(seed=0):
    e = EstimatorConfig(n_sc=16, lstm_hidden=8, hidden=8)
    return e, init_estimator(e, jax.random.PRNGKey(seed))


def _assert_identical(base, res):
    np.testing.assert_array_equal(base.splits, res.splits)
    np.testing.assert_array_equal(base.est_tp, res.est_tp)
    np.testing.assert_array_equal(np.nan_to_num(base.delay_s),
                                  np.nan_to_num(res.delay_s))


# ------------------------------------------------ bit-identity pins
def test_plain_engine_identical(prof, table):
    ep = _episode(8)
    base = simulate_fleet(ep, table, prof, CFG)
    res = simulate_fleet(ep, table, prof, CFG, telemetry=TelemetryConfig())
    _assert_identical(base, res)
    assert base.telemetry is None and res.telemetry is not None


def test_sched_engine_identical(prof, table):
    ep = _episode(8)
    cell = np.repeat((np.arange(8) % 2)[:, None], 6, axis=1).astype(np.int32)
    cell[:4, 3:] = 1 - cell[:4, 3:]  # mid-episode handover for 4 UEs
    kw = dict(sched=SchedulerConfig(policy="pf"), cell_idx=cell, n_cells=2)
    base = simulate_fleet(ep, table, prof, CFG, **kw)
    res = simulate_fleet(ep, table, prof, CFG,
                         telemetry=TelemetryConfig(), **kw)
    _assert_identical(base, res)
    np.testing.assert_array_equal(base.prb_share, res.prb_share)


def test_churn_pool_identical(prof, table):
    schedule, sessions = _churn()
    kw = dict(churn=schedule, capacity=16)
    base = simulate_fleet(sessions, table, prof, CFG, **kw)
    res = simulate_fleet(sessions, table, prof, CFG,
                         telemetry=TelemetryConfig(), **kw)
    _assert_identical(base, res)
    np.testing.assert_array_equal(base.active, res.active)
    rec = res.telemetry
    assert rec.admitted == base.lifecycle.n_admitted
    assert rec.departed == int(base.lifecycle.departed.sum())


def test_online_engine_identical_and_events(prof, table):
    est = _tiny_estimator()
    ep = _episode(8, T=10)
    ocfg = OnlineConfig(capacity=256, batch=16, steps=2, min_fill=8,
                        drift=DriftConfig(threshold_mbps=0.1,
                                          calibrate_periods=2, patience=1,
                                          cooldown=2))
    kw = dict(estimator=est, online=ocfg)
    base = simulate_fleet(ep, table, prof, CFG, **kw)
    res = simulate_fleet(ep, table, prof, CFG,
                         telemetry=TelemetryConfig(), **kw)
    _assert_identical(base, res)
    kinds = {e.kind for e in res.telemetry.events}
    # the untrained estimator's RMSE trips the absolute drift threshold
    assert "drift_trigger" in kinds and "burst_end" in kinds


# ------------------------------------------------ metric invariants
def _invariants(rec, res):
    n_act = (int(np.asarray(res.active).sum()) if res.active is not None
             else int(np.prod(res.splits.shape)))  # engine: all UEs live
    assert rec.active_steps == n_act
    for name in ("split", "err_mbps", "delay_s", "share"):
        assert sum(rec.hists[name]["counts"]) == rec.active_steps, name
    assert sum(rec.hists["occupancy"]["counts"]) == rec.periods
    assert rec.dropped_events == 0
    assert len(rec.series["occupancy"]) == rec.periods


def test_engine_invariants(prof, table):
    res = simulate_fleet(_episode(8), table, prof, CFG,
                         telemetry=TelemetryConfig())
    _invariants(res.telemetry, res)


def test_churn_invariants(prof, table):
    schedule, sessions = _churn()
    res = simulate_fleet(sessions, table, prof, CFG, churn=schedule,
                         capacity=16, telemetry=TelemetryConfig())
    _invariants(res.telemetry, res)
    admits = [e for e in res.telemetry.events if e.kind == "admit"]
    assert len(admits) == res.telemetry.admitted
    assert all(e.value >= 0 for e in admits)  # queue latency in periods


def test_event_ring_overflow_not_silent(prof, table):
    schedule, sessions = _churn()
    res = simulate_fleet(sessions, table, prof, CFG, churn=schedule,
                         capacity=16,
                         telemetry=TelemetryConfig(events_capacity=4))
    rec = res.telemetry
    assert len(rec.events) <= 4
    assert rec.dropped_events > 0  # overflow is counted, never silent


# ------------------------------------------- masked-step property tests
def _random_step_inputs(seed, s=16):
    rng = np.random.default_rng(seed)
    split = rng.integers(-1, 41, s).astype(np.int32)
    est = rng.uniform(0.5, 130.0, s).astype(np.float32)
    true = rng.uniform(0.5, 130.0, s).astype(np.float32)
    share = rng.uniform(0.0, 1.0, s).astype(np.float32)
    active = rng.random(s) < 0.6
    dconst = rng.uniform(0.01, 0.2, 42).astype(np.float32)
    dbytes = rng.uniform(1e3, 1e6, 42).astype(np.float32)
    return split, est, true, share, active, dconst, dbytes


def _step(cfg, ts, split, est, true, share, active, dconst, dbytes):
    return tel.telemetry_step(
        cfg, ts, period=0, split=jnp.asarray(split),
        est_tp=jnp.asarray(est), true_tp=jnp.asarray(true),
        share=jnp.asarray(share), active=jnp.asarray(active),
        dconst=jnp.asarray(dconst), dbytes=jnp.asarray(dbytes))


def check_inactive_contribute_nothing(seed):
    """Masked update == the same update on the compacted active rows."""
    cfg = TelemetryConfig()
    split, est, true, share, active, dconst, dbytes = \
        _random_step_inputs(seed)
    if not active.any():
        active[0] = True
    ts0 = tel.telemetry_init(cfg)
    masked, row_m = _step(cfg, ts0, split, est, true, share, active,
                          dconst, dbytes)
    a = active
    compact, row_c = _step(cfg, ts0, split[a], est[a], true[a], share[a],
                           np.ones(a.sum(), bool), dconst, dbytes)
    assert int(masked.active_steps) == int(compact.active_steps)
    for f in ("split_hist", "err_hist", "delay_hist", "share_hist"):
        np.testing.assert_array_equal(np.asarray(getattr(masked, f)),
                                      np.asarray(getattr(compact, f)))
    # per-slot stat channels (occupancy differs by construction: the
    # compacted pool has a different slot count)
    np.testing.assert_allclose(np.asarray(masked.sums)[:5],
                               np.asarray(compact.sums)[:5], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(masked.mins)[:5],
                                  np.asarray(compact.mins)[:5])
    np.testing.assert_array_equal(np.asarray(masked.maxs)[:5],
                                  np.asarray(compact.maxs)[:5])
    assert float(row_m.err_sq_sum) == pytest.approx(
        float(row_c.err_sq_sum), rel=1e-6)


def check_hist_totals(seed):
    cfg = TelemetryConfig()
    split, est, true, share, active, dconst, dbytes = \
        _random_step_inputs(seed)
    ts, _ = _step(cfg, tel.telemetry_init(cfg), split, est, true, share,
                  active, dconst, dbytes)
    n_act = int(active.sum())
    for f in ("split_hist", "err_hist", "delay_hist", "share_hist"):
        assert int(np.asarray(getattr(ts, f)).sum()) == n_act, f
    assert int(np.asarray(ts.occ_hist).sum()) == 1  # one sample/period


def check_ring_never_silent(seed, capacity):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(1, 24))
    valid = rng.random(k) < 0.7
    ring = tel.ring_init(capacity)
    ring = tel.ring_push(ring, jnp.full((k,), tel.EV_ADMIT, I32),
                         jnp.zeros((k,), I32), jnp.arange(k, dtype=I32),
                         jnp.zeros((k,), F32), jnp.asarray(valid))
    stored, dropped = int(ring.count), int(ring.dropped)
    assert stored <= capacity
    assert stored + dropped == int(valid.sum())  # every event accounted
    # stored lanes are the first valid ones, in lane order (keep-first)
    want = np.flatnonzero(valid)[:stored]
    np.testing.assert_array_equal(np.asarray(ring.arg)[:stored], want)


if HAVE_HYPOTHESIS:
    @hypothesis.settings(max_examples=15, deadline=None)
    @hypothesis.given(seed=st.integers(0, 10_000))
    def test_inactive_contribute_nothing(seed):
        check_inactive_contribute_nothing(seed)

    @hypothesis.settings(max_examples=15, deadline=None)
    @hypothesis.given(seed=st.integers(0, 10_000))
    def test_hist_totals(seed):
        check_hist_totals(seed)

    @hypothesis.settings(max_examples=15, deadline=None)
    @hypothesis.given(seed=st.integers(0, 10_000),
                      capacity=st.integers(1, 12))
    def test_ring_never_silent(seed, capacity):
        check_ring_never_silent(seed, capacity)
else:  # pragma: no cover - depends on environment
    @pytest.mark.parametrize("seed", range(8))
    def test_inactive_contribute_nothing(seed):
        check_inactive_contribute_nothing(seed)

    @pytest.mark.parametrize("seed", range(8))
    def test_hist_totals(seed):
        check_hist_totals(seed)

    @pytest.mark.parametrize("seed,capacity",
                             [(s, c) for s in range(4) for c in (1, 4, 12)])
    def test_ring_never_silent(seed, capacity):
        check_ring_never_silent(seed, capacity)


# ------------------------------------------------ decode + exporters
def test_record_roundtrip_and_exporters(prof, table, tmp_path):
    schedule, sessions = _churn()
    res = simulate_fleet(sessions, table, prof, CFG, churn=schedule,
                         capacity=16, telemetry=TelemetryConfig())
    rec = res.telemetry
    # dict round trip
    back = TelemetryRecord.from_dict(rec.to_dict())
    assert back.admitted == rec.admitted
    assert back.active_steps == rec.active_steps
    assert [e.kind for e in back.events] == [e.kind for e in rec.events]
    # JSON lines: one line per period + the summary line
    path = tmp_path / "run.jsonl"
    to_jsonl(rec, str(path))
    lines = path.read_text().strip().splitlines()
    assert len(lines) == rec.periods + 1
    summary = json.loads(lines[-1])["summary"]
    assert summary["admitted"] == rec.admitted
    # Prometheus text exposition: counters + cumulative histogram
    prom = to_prometheus(rec)
    assert f"fleet_admitted_total {rec.admitted}" in prom
    assert 'le="+Inf"' in prom
    # the +Inf bucket of each histogram equals its _count
    for name in ("split", "err_mbps"):
        total = sum(rec.hists[name]["counts"])
        assert f'fleet_{name}_count {total}' in prom


def test_event_timeline_filter(prof, table):
    schedule, sessions = _churn()
    rec = simulate_fleet(sessions, table, prof, CFG, churn=schedule,
                         capacity=16,
                         telemetry=TelemetryConfig()).telemetry
    only = rec.event_timeline(("admit",))
    assert only and all(e.kind == "admit" for e in only)
    periods = [e.period for e in rec.events]
    assert periods == sorted(periods)  # decode sorts by period


# ------------------------------------------------ stage-timing helpers
def test_timed_and_stages():
    stat = timed(lambda: None, reps=3)
    assert stat.best >= 0 and stat.median >= stat.best >= 0
    assert stat.spread >= 0
    assert set(stat.ms()) == {"best_ms", "median_ms", "spread_ms"}


def test_stopwatch():
    from benchmarks.common import stopwatch
    with stopwatch() as sw:
        sum(range(1000))
    assert sw.seconds > 0
