"""Closed-loop online estimator adaptation (repro.sim.online).

Pins the four load-bearing properties of the subsystem: (1) replay-buffer
ring semantics (wrap, overwrite-oldest, batch > capacity), (2)
drift-trigger hysteresis (calibration never fires; patience and cooldown
gate triggers), (3) the sharded and unsharded adaptation steps are
numerically interchangeable (data-sharded batch + psum'd grads == single
device), and (4) ``simulate_fleet(online=None)`` is bit-identical to the
PR 4 engine program.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel import scenarios as sc
from repro.core.controller import ControllerConfig
from repro.core.pso import LookupTable
from repro.estimator.model import EstimatorConfig, init_estimator
from repro.estimator.train import make_indexed_step
from repro.models.vgg import FULL, vgg_split_profile
from repro.optim import AdamW
from repro.sim import (DriftConfig, OnlineConfig, buffer_add, buffer_count,
                       buffer_data, buffer_init, drift_init, drift_step,
                       drift_threshold, emit_period_samples, estimate_fleet,
                       make_serving_mesh, online_estimate_fleet,
                       run_controllers, simulate_fleet, split_metrics)

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs >= 8 (virtual) devices")

N_SC_TEST = 16


def tiny_estimator(seed: int = 0):
    e = EstimatorConfig(n_sc=N_SC_TEST, lstm_hidden=8, hidden=8)
    return e, init_estimator(e, jax.random.PRNGKey(seed))


def episode(n: int, T: int = 6, seed: int = 5):
    rng = np.random.default_rng(seed)
    names = np.asarray(sc.SCENARIOS)[np.arange(n) % len(sc.SCENARIOS)]
    return sc.gen_episode_batch(names, T, rng, n_sc=N_SC_TEST)


def fig6_style_table(prof):
    return LookupTable(ue_name="t", table=np.full(41, 3, np.int32),
                       tp_min_mbps=np.zeros(len(prof.data_bytes)),
                       feasible_prefilter=np.ones(len(prof.data_bytes),
                                                  bool))


# ----------------------------------------------------------------- buffer
def test_buffer_ring_semantics():
    """Wrap-around overwrites the OLDEST rows; count saturates at cap."""
    e, _ = tiny_estimator()
    buf = buffer_init(8, e)
    assert buf.capacity == 8 and buffer_count(buf) == 0

    def rows(lo, n):
        tp = np.arange(lo, lo + n, dtype=np.float32)
        kpms = np.tile(tp[:, None, None], (1, e.window, e.n_kpms))
        iq = np.tile(tp[:, None, None, None], (1, 2, e.n_sc, e.n_sym))
        return kpms, iq, tp * 0.01, tp

    buf = buffer_add(buf, *rows(0, 5))
    assert buffer_count(buf) == 5 and int(buf.head) == 5
    np.testing.assert_array_equal(np.asarray(buf.tp[:5]), np.arange(5))
    # 5 more: slots 5..7 then wrap to 0..1 — rows 0 and 1 (oldest) die
    buf = buffer_add(buf, *rows(5, 5))
    assert buffer_count(buf) == 8 and int(buf.head) == 2
    np.testing.assert_array_equal(
        np.asarray(buf.tp), [8, 9, 2, 3, 4, 5, 6, 7])
    # every field moves together (same ring positions)
    np.testing.assert_array_equal(np.asarray(buf.kpms[:, 0, 0]),
                                  np.asarray(buf.tp))
    np.testing.assert_allclose(np.asarray(buf.alloc),
                               np.asarray(buf.tp) * 0.01, rtol=1e-6)


def test_buffer_add_larger_than_capacity_keeps_newest():
    """A batch > capacity keeps exactly the newest ``capacity`` rows (the
    scatter must never see duplicate indices)."""
    e, _ = tiny_estimator()
    buf = buffer_init(4, e)
    kpms = np.zeros((10, e.window, e.n_kpms), np.float32)
    iq = np.zeros((10, 2, e.n_sc, e.n_sym), np.float32)
    buf = buffer_add(buf, kpms, iq, np.zeros(10, np.float32),
                     np.arange(10, dtype=np.float32))
    assert buffer_count(buf) == 4
    assert sorted(np.asarray(buf.tp).tolist()) == [6, 7, 8, 9]
    data = buffer_data(buf)
    assert set(data) == {"kpms", "iq", "alloc", "tp"}


@multi_device
def test_buffer_sharded_over_data_axis():
    """Under a serving mesh the buffer's row axis is committed on the
    mesh's data axis (the batch rule), not replicated."""
    from jax.sharding import PartitionSpec as P
    e, _ = tiny_estimator()
    serving = make_serving_mesh("8x1")
    buf = buffer_init(16, e, serving=serving)
    assert buf.iq.sharding.spec == P("data", None, None, None)
    assert buf.kpms.sharding.spec == P("data", None, None)
    assert buf.tp.sharding.spec == P("data")


# ---------------------------------------------------------- drift monitor
def test_drift_calibration_never_fires_and_sets_baseline():
    cfg = DriftConfig(calibrate_periods=4, ratio=1.5, patience=1, cooldown=0)
    st = drift_init()
    for r in (10.0, 12.0, 8.0, 10.0):  # huge values: would fire if armed
        st, fired = drift_step(cfg, st, r)
        assert not fired
    assert st.baseline == pytest.approx(10.0)
    assert drift_threshold(cfg, st) == pytest.approx(15.0)


def test_drift_trigger_hysteresis():
    """patience gates the trigger: one noisy period is not drift; a
    sustained exceedance fires exactly once, then cooldown disarms."""
    cfg = DriftConfig(alpha=1.0, calibrate_periods=2, ratio=1.5,
                      patience=2, cooldown=3)
    st = drift_init()
    for r in (10.0, 10.0):  # calibrate: baseline 10, threshold 15
        st, fired = drift_step(cfg, st, r)
    # a single spike (patience=2) must NOT fire
    st, fired = drift_step(cfg, st, 40.0)
    assert not fired and st.above == 1
    st, fired = drift_step(cfg, st, 12.0)  # back below: streak resets
    assert not fired and st.above == 0
    # sustained exceedance: fires on the 2nd consecutive period
    st, fired = drift_step(cfg, st, 40.0)
    assert not fired
    st, fired = drift_step(cfg, st, 40.0)
    assert fired and st.n_triggers == 1 and st.cooldown_left == 3
    # cooldown: still way above threshold, but disarmed for 3 periods
    for _ in range(3):
        st, fired = drift_step(cfg, st, 40.0)
        assert not fired
    # re-armed: the streak must build up again (patience from zero)
    st, fired = drift_step(cfg, st, 40.0)
    assert not fired
    st, fired = drift_step(cfg, st, 40.0)
    assert fired and st.n_triggers == 2


def test_drift_unarmed_holds_streak_without_consuming_trigger():
    """armed=False (buffer below min_fill) must not swallow a trigger:
    the streak holds at patience — no cooldown, no n_triggers — and the
    first armed period fires immediately."""
    cfg = DriftConfig(alpha=1.0, calibrate_periods=1, ratio=1.5,
                      patience=2, cooldown=3)
    st = drift_init()
    st, _ = drift_step(cfg, st, 10.0)  # calibrate: threshold 15
    for _ in range(4):  # sustained drift, but the caller can't act yet
        st, fired = drift_step(cfg, st, 40.0, armed=False)
        assert not fired
    assert st.above == cfg.patience and st.n_triggers == 0
    assert st.cooldown_left == 0
    st, fired = drift_step(cfg, st, 40.0, armed=True)
    assert fired and st.n_triggers == 1  # acts the moment it can


def test_online_min_fill_defers_first_burst():
    """A trigger raised while the buffer is under min_fill is deferred,
    not lost: the burst runs on the first period the buffer is ready,
    and checkpoint steps stay 1..n_adaptations."""
    e, params = tiny_estimator()
    ep = episode(4, T=10)  # 4 rows/period: min_fill=16 ready at t=3
    ocfg = OnlineConfig(capacity=64, batch=8, steps=2, min_fill=16,
                        drift=DriftConfig(calibrate_periods=1,
                                          threshold_mbps=0.0, patience=1,
                                          cooldown=99))
    est, stats = online_estimate_fleet(ep, (e, params), ocfg)
    # patience satisfied from t=1 on, but fill(t)=4(t+1): first armed
    # period is t=3 — exactly one burst (cooldown then covers the rest)
    assert stats.n_adaptations == 1
    np.testing.assert_array_equal(np.nonzero(stats.adapted)[0], [3])
    assert stats.ckpt_steps == []


def test_drift_absolute_threshold_override():
    cfg = DriftConfig(calibrate_periods=1, threshold_mbps=5.0, patience=1,
                      cooldown=0)
    st = drift_init()
    st, fired = drift_step(cfg, st, 100.0)  # calibration period
    assert not fired
    st, fired = drift_step(cfg, st, 6.0)
    assert fired  # 6 > 5 regardless of the (huge) calibrated baseline


# ------------------------------------------------- sharded vs unsharded
@multi_device
def test_sharded_vs_unsharded_step_allclose():
    """One adaptation step under the serving mesh (data-sharded batch,
    replicated params, psum'd grads) == the single-device step: same loss,
    same updated params to float tolerance."""
    e, params = tiny_estimator()
    serving = make_serving_mesh("8x1")
    opt = AdamW(lr=1e-3, weight_decay=1e-4, clip_norm=1.0)
    rng = np.random.default_rng(1)
    data = {"kpms": jnp.asarray(rng.normal(size=(32, e.window, e.n_kpms)),
                                jnp.float32),
            "iq": jnp.asarray(rng.normal(size=(32, 2, e.n_sc, e.n_sym)),
                              jnp.float32),
            "alloc": jnp.asarray(rng.uniform(size=32), jnp.float32),
            "tp": jnp.asarray(rng.uniform(10, 100, 32), jnp.float32)}
    idx = jnp.asarray(rng.integers(0, 32, 16), jnp.int32)
    key = jax.random.PRNGKey(7)
    plain = make_indexed_step(e, opt)
    shard = make_indexed_step(e, opt, mesh=serving.mesh,
                              overrides=serving.rule_overrides())
    p0, _, l0 = plain(params, opt.init(params), data, idx, key)
    p1, _, l1 = shard(params, opt.init(params), data, idx, key)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@multi_device
def test_online_sharded_matches_unsharded_loop():
    """The whole closed loop under a serving mesh tracks the unsharded
    loop: same estimates (allclose) and the same adaptation schedule."""
    e, params = tiny_estimator()
    ep = episode(8, T=6)
    ocfg = OnlineConfig(capacity=64, batch=16, steps=3, min_fill=8,
                        drift=DriftConfig(calibrate_periods=2,
                                          threshold_mbps=0.0, patience=1,
                                          cooldown=1))
    est_u, st_u = online_estimate_fleet(ep, (e, params), ocfg)
    est_s, st_s = online_estimate_fleet(ep, (e, params), ocfg,
                                        serving=make_serving_mesh("8x1"))
    np.testing.assert_allclose(est_s, est_u, rtol=1e-4, atol=1e-3)
    np.testing.assert_array_equal(st_s.adapted, st_u.adapted)
    assert st_s.n_adaptations == st_u.n_adaptations > 0


# --------------------------------------------------- engine bit-identity
def test_online_none_is_bit_identical_to_pr4_program():
    """simulate_fleet(online=None) must BE the PR 4 program: the same
    estimates, splits and metrics as the manual estimate_fleet ->
    run_controllers -> split_metrics composition, bit for bit."""
    e, params = tiny_estimator()
    ep = episode(8, T=5)
    prof = vgg_split_profile(FULL)
    table = fig6_style_table(prof)
    cfg = ControllerConfig(0.5, 2, 3)
    res = simulate_fleet(ep, table, prof, cfg, estimator=(e, params),
                         online=None)
    # the PR 4 composition, spelled out
    est = estimate_fleet(ep, (e, params))
    tables = np.broadcast_to(table.table, (ep.n_ues, len(table.table)))
    splits = run_controllers(tables, est, cfg, cfg.fallback_split)
    delay, priv, energy = split_metrics(prof, splits,
                                        np.asarray(ep.tp_mbps, float))
    np.testing.assert_array_equal(res.est_tp, est)
    np.testing.assert_array_equal(res.splits, splits)
    np.testing.assert_array_equal(res.delay_s, delay)
    np.testing.assert_array_equal(res.privacy, priv)
    np.testing.assert_array_equal(res.energy_j, energy)
    assert res.online is None
    # and the kwarg default is the same code path
    res2 = simulate_fleet(ep, table, prof, cfg, estimator=(e, params))
    np.testing.assert_array_equal(res2.splits, res.splits)
    np.testing.assert_array_equal(res2.est_tp, res.est_tp)


def test_emit_period_samples_matches_episode():
    ep = episode(4, T=5)
    wins = ep.kpm_windows(normalize=True).astype(np.float32)
    s = emit_period_samples(ep, 3)
    np.testing.assert_array_equal(s["kpms"], wins[:, 3])
    np.testing.assert_array_equal(s["iq"], ep.iq[:, 3].astype(np.float32))
    np.testing.assert_array_equal(s["alloc"],
                                  ep.alloc_ratio.astype(np.float32))
    np.testing.assert_array_equal(s["tp"],
                                  ep.tp_mbps[:, 3].astype(np.float32))


# ------------------------------------------------------- adaptation loop
def test_online_adapts_reduces_rmse_and_checkpoints(tmp_path):
    """The closed loop actually learns: with a forced trigger cadence the
    adapted estimator's late-episode RMSE beats the frozen estimator's,
    loss falls across bursts, and every burst lands a checkpoint."""
    e, params = tiny_estimator()
    ep = episode(16, T=16, seed=9)
    ocfg = OnlineConfig(capacity=256, batch=64, steps=10, lr=3e-3,
                        min_fill=16, seed=1,
                        drift=DriftConfig(calibrate_periods=2,
                                          threshold_mbps=0.0, patience=1,
                                          cooldown=1),
                        ckpt_dir=str(tmp_path / "online_ckpt"),
                        ckpt_keep=2)
    frozen = estimate_fleet(ep, (e, params))
    est, stats = online_estimate_fleet(ep, (e, params), ocfg)
    assert stats.n_adaptations >= 3
    assert stats.train_steps == stats.n_adaptations * ocfg.steps
    # the last bursts must fit better than the first
    assert stats.train_loss[-1] < stats.train_loss[0]
    # late-episode RMSE: adapted beats frozen (random-init params are far
    # off; a few bursts on live labels must close most of the gap)
    tp = np.asarray(ep.tp_mbps, float)
    late = slice(ep.n_steps // 2, None)
    rmse_onl = float(np.sqrt(np.mean((est[:, late] - tp[:, late]) ** 2)))
    rmse_frz = float(np.sqrt(np.mean((frozen[:, late] - tp[:, late]) ** 2)))
    assert rmse_onl < rmse_frz
    # checkpoints: one per burst, pruned to ckpt_keep, restorable
    from repro.checkpoint import CheckpointManager
    assert stats.ckpt_steps == list(range(1, stats.n_adaptations + 1))
    mgr = CheckpointManager(ocfg.ckpt_dir, keep=ocfg.ckpt_keep)
    assert mgr.latest() == stats.n_adaptations
    restored, step = mgr.restore(params)
    assert step == stats.n_adaptations
    for a, b in zip(jax.tree.leaves(restored),
                    jax.tree.leaves(stats.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_online_no_trigger_means_frozen_estimates():
    """With the monitor never tripping (huge absolute threshold) the loop
    degenerates to the frozen per-period predict: estimates equal
    estimate_fleet's and no train step runs."""
    e, params = tiny_estimator()
    ep = episode(4, T=4)
    ocfg = OnlineConfig(capacity=32, batch=8, steps=2, min_fill=4,
                        drift=DriftConfig(calibrate_periods=1,
                                          threshold_mbps=1e9, patience=1))
    est, stats = online_estimate_fleet(ep, (e, params), ocfg)
    np.testing.assert_allclose(est, estimate_fleet(ep, (e, params)),
                               rtol=1e-6, atol=1e-6)
    assert stats.n_adaptations == 0 and stats.train_steps == 0
    assert stats.ckpt_steps == []


def test_simulate_fleet_online_hook():
    """The engine hook returns a FleetResult whose controllers consumed
    the adapted estimates, with the adaptation trace attached."""
    e, params = tiny_estimator()
    ep = episode(8, T=8)
    prof = vgg_split_profile(FULL)
    table = fig6_style_table(prof)
    cfg = ControllerConfig(0.5, 2, 3)
    ocfg = OnlineConfig(capacity=64, batch=16, steps=4, min_fill=8,
                        drift=DriftConfig(calibrate_periods=2,
                                          threshold_mbps=0.0, patience=1,
                                          cooldown=1))
    res = simulate_fleet(ep, table, prof, cfg, estimator=(e, params),
                         online=ocfg, fixed_split=3)
    assert res.online is not None and res.online.n_adaptations > 0
    assert res.online.rmse.shape == (ep.n_steps,)
    # the splits are the controller scan over the adapted estimates
    tables = np.broadcast_to(table.table, (ep.n_ues, len(table.table)))
    np.testing.assert_array_equal(
        res.splits, run_controllers(tables, res.est_tp, cfg, 3))
    # ValueError, not assert: the guard must survive python -O
    with pytest.raises(ValueError, match="needs an estimator"):
        simulate_fleet(ep, table, prof, cfg, online=ocfg)


def test_online_config_frozen_and_hashable():
    """OnlineConfig/DriftConfig key lru caches (the step-program cache):
    they must stay frozen and hashable."""
    a = OnlineConfig()
    b = dataclasses.replace(a, steps=7)
    assert hash(a) != () and a != b
    assert hash(DriftConfig()) == hash(DriftConfig())


# ---------------------------------------------------- the shared adapter
def _adapter_run(kind: str, loop: str, ckpt_dir: str):
    """One tiny online ``simulate_fleet`` run of the ``kind`` estimator
    (windowed or recurrent) on the ``loop`` path (batch or slot pool),
    with a forced trigger, telemetry and checkpoints on."""
    from repro.estimator.ssm import SSMConfig, init_ssm
    from repro.sim import TelemetryConfig
    rng = np.random.default_rng(41)
    if kind == "ssm":
        c = SSMConfig(n_heads=2, head_dim=4, state_dim=4, hidden=8)
        est = (c, init_ssm(c, jax.random.PRNGKey(0)))
    else:
        est = tiny_estimator()
    kw = {}
    if loop == "pool":
        T, capacity = 10, 6
        schedule = sc.make_churn_schedule(
            sc.ChurnConfig(arrival_rate=2.0, mean_dwell=4.0, max_dwell=6),
            T, rng)
        n, steps = schedule.n_sessions, schedule.max_dwell
        kw = {"churn": schedule, "capacity": capacity}
    else:
        n, steps = 8, 10
    names = np.asarray(sc.SCENARIOS)[np.arange(n) % len(sc.SCENARIOS)]
    ep = sc.gen_episode_batch(names, steps, rng, n_sc=N_SC_TEST,
                              include_iq=kind == "lstm")
    ocfg = OnlineConfig(capacity=64, batch=8, steps=3, min_fill=8, seed=2,
                        drift=DriftConfig(calibrate_periods=2,
                                          threshold_mbps=0.0, patience=1,
                                          cooldown=1),
                        ckpt_dir=ckpt_dir, ckpt_keep=2)
    prof = vgg_split_profile(FULL)
    return ocfg, simulate_fleet(
        ep, fig6_style_table(prof), prof, ControllerConfig(0.5, 2, 3),
        estimator=est, online=ocfg, telemetry=TelemetryConfig(), **kw)


@pytest.mark.parametrize("kind,loop", [("lstm", "batch"), ("lstm", "pool"),
                                       ("ssm", "batch"), ("ssm", "pool")])
def test_online_adapter_contract(kind, loop, tmp_path):
    """All four online loops (windowed or recurrent, batch or pool) adapt
    through the one ``OnlineAdapter``: ``ocfg.steps`` AdamW steps and one
    checkpoint per trigger, one burst_start/burst_end event pair per
    adapted period, and a seeded run that repeats exactly."""
    from repro.checkpoint import CheckpointManager
    ocfg, res = _adapter_run(kind, loop, str(tmp_path / "a"))
    st = res.online
    assert st.n_adaptations > 0
    assert st.train_steps == st.n_adaptations * ocfg.steps
    assert len(st.train_loss) == st.n_adaptations
    assert st.ckpt_steps == list(range(1, st.n_adaptations + 1))
    assert CheckpointManager(ocfg.ckpt_dir).latest() == st.n_adaptations
    adapted = list(np.flatnonzero(st.adapted))
    tl = res.telemetry
    for name in ("drift_trigger", "burst_start", "burst_end"):
        assert [e.period for e in tl.event_timeline([name])] == adapted
    assert [e.arg for e in tl.event_timeline(["burst_end"])] == (
        [ocfg.steps] * st.n_adaptations)
    _, again = _adapter_run(kind, loop, str(tmp_path / "b"))
    for a, b in ((res.splits, again.splits), (res.est_tp, again.est_tp),
                 (st.rmse, again.online.rmse),
                 (st.adapted, again.online.adapted),
                 (st.train_loss, again.online.train_loss)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(st.params),
                    jax.tree.leaves(again.online.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
