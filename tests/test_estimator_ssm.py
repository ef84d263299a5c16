"""Recurrent SSM estimator tests (repro.estimator.ssm + sim wiring).

Pins the load-bearing contracts of the recurrent path: (1) a scan of
O(1) ``ssm_step`` updates reproduces the chunked ``ssm_forward_seq``
pass (same params, different accumulation order); (2)
``forecast_horizon=0`` is BIT-identical to the plain 1-step estimate
under every forecast policy — forecasting is strictly additive; (3) the
engine/serving/online/pool integrations agree with each other and
refuse the windowed-estimator-only switches (int8 serving, quantized
ring) with actionable errors; and (4) the default LSTM estimator's
plain/sched/churn/online paths are pinned bit-identical to the PR 7
program via test-local reimplementations, so the SSM dispatch can never
silently perturb them.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.channel import kpm as kpmmod
from repro.channel import scenarios as sc
from repro.channel.scenarios import WINDOW
from repro.core.controller import ControllerConfig
from repro.core.pso import LookupTable
from repro.estimator.model import EstimatorConfig, init_estimator
from repro.estimator.ssm import (N_IQ_FEATS, SSMConfig, episode_features,
                                 init_ssm, iq_columns, iq_features,
                                 reduce_forecasts, report_alloc,
                                 report_features, rounded_quotient,
                                 ssm_forward_seq, ssm_state_init, ssm_step,
                                 ssm_warm_state)
from repro.estimator.train import fwd, ssm_predict, train_ssm
from repro.models.vgg import FULL, vgg_split_profile
from repro.optim import AdamW
from repro.sim import (DriftConfig, OnlineConfig, SchedulerConfig, buffer_add,
                       buffer_count, buffer_data, buffer_init, drift_init,
                       drift_step, emit_period_samples, estimate_fleet,
                       make_serving_mesh, online_estimate_fleet,
                       online_step_program, run_controllers, run_scheduled,
                       simulate_fleet)
from repro.sim import engine

multi_device = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs >= 8 (virtual) devices")

N_SC_TEST = 16
I32 = jnp.int32


def tiny_ssm(seed: int = 0, **kw):
    c = SSMConfig(n_heads=2, head_dim=4, state_dim=4, hidden=8, **kw)
    return c, init_ssm(c, jax.random.PRNGKey(seed))


def tiny_lstm(seed: int = 0):
    e = EstimatorConfig(n_sc=N_SC_TEST, lstm_hidden=8, hidden=8)
    return e, init_estimator(e, jax.random.PRNGKey(seed))


def episode(n: int, T: int = 8, seed: int = 5, iq: bool = False):
    rng = np.random.default_rng(seed)
    names = np.asarray(sc.SCENARIOS)[np.arange(n) % len(sc.SCENARIOS)]
    return sc.gen_episode_batch(names, T, rng, n_sc=N_SC_TEST,
                                include_iq=iq)


def fig6_style_table(prof):
    return LookupTable(ue_name="t", table=np.full(41, 3, np.int32),
                       tp_min_mbps=np.zeros(len(prof.data_bytes)),
                       feasible_prefilter=np.ones(len(prof.data_bytes),
                                                  bool))


def _full_pool_schedule(n, T):
    return sc.ChurnSchedule(arrival_t=np.zeros(n, np.int32),
                            dwell=np.full(n, T, np.int32),
                            ready_end=np.full(T, n, np.int32),
                            horizon=T, max_admits=n)


# ---------------------------------------------------------- core module
@pytest.mark.parametrize("mode", ["sequence", "step"])
def test_contractions_trace_at_fp32(mode):
    """Every contraction of both modes (projections, mixer, readout,
    forecast rollout) is traced at ``highest`` precision whatever the
    process default, so a backend whose default rounds f32 operands to
    bf16 computes the same estimate as the fp32 ssd kernel."""
    import re
    c, p = tiny_ssm(forecast_horizon=2)
    f = jnp.zeros((2, 8, c.n_feats), jnp.float32)
    with jax.default_matmul_precision("bfloat16"):
        if mode == "sequence":
            jaxpr = jax.make_jaxpr(
                lambda p, f: ssm_forward_seq(c, p, f))(p, f)
        else:
            jaxpr = jax.make_jaxpr(lambda p, s, f: ssm_step(c, p, s, f))(
                p, ssm_state_init(c, (2,)), f[:, 0])
    text = str(jaxpr)
    # dot_general is the only primitive here that carries a precision
    precisions = re.findall(r"precision=(\S+)", text)
    assert len(precisions) == text.count("dot_general[") >= 5
    assert set(precisions) == {"(Precision.HIGHEST,"}


def test_config_validation_and_state_accounting():
    with pytest.raises(ValueError, match="n_heads"):
        SSMConfig(n_heads=3, n_groups=2)
    with pytest.raises(ValueError, match="forecast_policy"):
        SSMConfig(forecast_policy="mean")
    with pytest.raises(ValueError, match="forecast_horizon"):
        SSMConfig(forecast_horizon=-1)
    c = SSMConfig()
    assert c.state_shape() == (1, 4, 8, 8)
    assert c.state_bytes() == 4 * 8 * 8 * 4  # f32
    assert c.n_feats == 16
    # hashable: the configs key jit static args and lru caches
    assert hash(c) == hash(SSMConfig())
    assert c != dataclasses.replace(c, forecast_horizon=2)


def test_episode_features_layout():
    ep = episode(3, T=5)
    feats = episode_features(ep.kpms, ep.alloc_ratio)
    assert feats.shape == (3, 5 + WINDOW, 16)
    assert feats.dtype == np.float32
    # channel 15 is the clipped alloc ratio, constant over the trace
    np.testing.assert_allclose(
        feats[..., -1],
        np.broadcast_to(np.clip(ep.alloc_ratio, 0, 1)[:, None],
                        feats.shape[:2]), rtol=1e-6)


def test_episode_features_iq_channels():
    """``include_iq`` appends exactly ``N_IQ_FEATS`` summary channels:
    zeros over the warm-up prefix (no estimate is read there), period
    ``t``'s ``iq_features`` on the index the estimator reads for period
    ``t`` (WINDOW-1+t), KPM/alloc channels untouched."""
    assert SSMConfig(include_iq=True).n_feats == 16 + N_IQ_FEATS
    ep = episode(2, T=5, iq=True)
    base = episode_features(ep.kpms, ep.alloc_ratio)
    feats = episode_features(ep.kpms, ep.alloc_ratio, ep.iq)
    assert feats.shape == (2, 5 + WINDOW, 16 + N_IQ_FEATS)
    np.testing.assert_array_equal(feats[..., :16], base)
    np.testing.assert_array_equal(feats[:, :WINDOW - 1, 16:], 0.0)
    np.testing.assert_array_equal(feats[:, WINDOW - 1 + 5:, 16:], 0.0)
    np.testing.assert_array_equal(feats[:, WINDOW - 1:WINDOW - 1 + 5, 16:],
                                  iq_features(ep.iq))
    with pytest.raises(ValueError, match="periods"):
        episode_features(ep.kpms[:, :WINDOW], ep.alloc_ratio, ep.iq)


def test_include_iq_estimate_and_missing_iq_guard():
    """``include_iq=True`` through ``estimate_fleet`` == the manual
    sequence pass over IQ-augmented features, and an episode generated
    WITHOUT spectrograms is refused with an actionable error (instead of
    silently serving zero IQ channels)."""
    c, params = tiny_ssm(seed=3, include_iq=True)
    ep = episode(4, T=6, iq=True)
    got = estimate_fleet(ep, (c, params))
    fc, _ = ssm_forward_seq(
        c, params,
        jnp.asarray(episode_features(ep.kpms, ep.alloc_ratio, ep.iq)))
    want = np.clip(reduce_forecasts(
        c, np.asarray(fc[:, WINDOW - 1:WINDOW - 1 + ep.n_steps])), 1.0, 130.0)
    np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="include_iq"):
        estimate_fleet(episode(4, T=6), (c, params))
    with pytest.raises(ValueError, match="include_iq"):
        online_estimate_fleet(episode(4, T=6), (c, params), OnlineConfig())


def _host_features(kpms, alloc, iq=None):
    """The report features in f32 NumPy: normalized KPMs, the clipped
    alloc ratio broadcast as channel 15, then the IQ columns."""
    k = (np.asarray(kpms, np.float32) - kpmmod.KPM_CENTER) / kpmmod.KPM_SCALE
    a = np.clip(np.asarray(alloc, np.float32), 0.0, 1.0)
    cols = [k, np.broadcast_to(a[:, None, None], k.shape[:2] + (1,))]
    if iq is not None:
        cols.append(iq_columns(iq, k.shape[1]))
    return np.concatenate(cols, -1)


@pytest.mark.parametrize("flat", [False, True])
@pytest.mark.parametrize("n,s", [(1, WINDOW + 1), (7, 45), (300, 56),
                                 (engine.SSM_CHUNK_UES // 64, 56)])
def test_report_features_bit_equal_to_numpy(n, s, flat):
    """The device features == the f32 NumPy normalisation bit for bit on
    the CPU, from shaped or flat reports, alloc ratios below 0 and above 1
    clipped alike, at shapes down to a chunk far shorter than
    ``SSM_CHUNK_UES``."""
    rng = np.random.default_rng(n + s)
    kpms = (kpmmod.KPM_CENTER + kpmmod.KPM_SCALE
            * rng.normal(size=(n, s, 15))).astype(np.float32)
    alloc = rng.uniform(-0.5, 1.5, n)
    alloc[:2] = (-0.25, 1.25)[:n]
    per_report = report_alloc(alloc, s)
    assert per_report.shape == (n * s,) and per_report.dtype == np.float32
    x = kpms.reshape(-1) if flat else kpms
    a = per_report if flat else per_report.reshape(n, s)
    got = report_features((n, s), jnp.asarray(x), jnp.asarray(a),
                          kpmmod.KPM_CENTER, kpmmod.KPM_SCALE)
    want = _host_features(kpms, alloc)
    assert got.shape == (n, s, 16) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(episode_features(kpms, alloc), want)
    assert ((want[..., 15] >= 0) & (want[..., 15] <= 1)).all()


@pytest.mark.parametrize("off", ["reciprocal", -2, -1, 1, 2])
def test_rounded_quotient_restores_numpy_division(off):
    """From a quotient an ulp or two off — or formed as XLA's TPU backend
    forms it, times the divisor's reciprocal — ``rounded_quotient`` gives
    NumPy's correctly rounded f32 division, over normalized KPMs and over
    values from 1e-3 to 1e3 of either sign."""
    rng = np.random.default_rng(11)
    s = kpmmod.KPM_SCALE
    ep = episode(64, T=26, seed=31)
    mag = np.float32(10) ** rng.integers(-3, 4, (20000, 15))
    for d in (ep.kpms.reshape(-1, 15) - kpmmod.KPM_CENTER,
              (rng.normal(size=(20000, 15)) * mag).astype(np.float32)):
        want = d / s
        if off == "reciprocal":
            q = d * (np.float32(1) / s)
            assert (q != want).mean() > 0.05  # the case it exists for
        else:
            q = want
            for _ in range(abs(off)):
                q = np.nextafter(q, np.float32(np.sign(off) * np.inf))
        got = rounded_quotient(jnp.asarray(q), jnp.asarray(d),
                               jnp.asarray(s))
        np.testing.assert_array_equal(np.asarray(got), want)


def test_report_features_iq_columns():
    """``include_iq`` columns through the device path: ``iq_columns`` as
    the ``extra`` operand lands after channel 15, bit-equal to the host
    concatenation, and ``episode_features`` with ``iq`` is the same."""
    ep = episode(3, T=5, iq=True)
    want = _host_features(ep.kpms, ep.alloc_ratio, ep.iq)
    n, s = ep.kpms.shape[:2]
    got = report_features(
        (n, s), jnp.asarray(ep.kpms.reshape(-1)),
        jnp.asarray(report_alloc(ep.alloc_ratio, s)),
        kpmmod.KPM_CENTER, kpmmod.KPM_SCALE,
        jnp.asarray(iq_columns(ep.iq, s)))
    assert got.shape == (3, 5 + WINDOW, 16 + N_IQ_FEATS)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        episode_features(ep.kpms, ep.alloc_ratio, ep.iq), want)


def _in_range(params):
    """The readout scaled and shifted so the estimates land inside the
    1..130 Mbps clip (a random readout gives about -0.6..0.5 Mbps, which
    the clip maps to one value)."""
    head = params["head"]
    return dict(params, head=dict(head, w2=head["w2"] * 50.0,
                                  b2=head["b2"] + 60.0))


def _manual_estimate(ep, c, params, chunk):
    """Host features -> ``ssm_forward_seq`` per ``chunk`` UEs -> the
    WINDOW-1 alignment slice -> policy reduce -> clip."""
    feats = _host_features(ep.kpms, ep.alloc_ratio,
                           ep.iq if c.include_iq else None)
    est = []
    for i in range(0, ep.n_ues, chunk):
        fc, _ = ssm_forward_seq(c, params, jnp.asarray(feats[i:i + chunk]))
        est.append(reduce_forecasts(
            c, np.asarray(fc[:, WINDOW - 1:WINDOW - 1 + ep.n_steps])))
    return np.clip(np.concatenate(est), 1.0, 130.0)


@pytest.mark.parametrize("iq", [False, True])
def test_estimate_fleet_ssm_across_chunks(iq, monkeypatch):
    """With ``SSM_CHUNK_UES`` lowered to cut the fleet into two whole
    chunks and a short one, the engine's device-built features give the
    manual sequence pass over host features, bit for bit."""
    monkeypatch.setattr(engine, "SSM_CHUNK_UES", 4)
    c, params = tiny_ssm(seed=4, forecast_horizon=2, forecast_policy="min",
                         include_iq=iq)
    params = _in_range(params)
    ep = episode(10, T=6, seed=21, iq=iq)
    got = estimate_fleet(ep, (c, params))
    assert ((got > 1.0) & (got < 130.0)).all()
    np.testing.assert_array_equal(got, _manual_estimate(ep, c, params, 4))


@pytest.mark.parametrize("sched", [None, "pf"])
def test_pool_ssm_matches_manual_across_chunks(sched, monkeypatch):
    """A churned pool with the recurrent estimator, its sessions cut into
    several chunks, == the same pool fed the manual estimates over host
    features: identical splits, estimates, occupancy and shares."""
    monkeypatch.setattr(engine, "SSM_CHUNK_UES", 6)
    rng = np.random.default_rng(23)
    schedule = sc.make_churn_schedule(
        sc.ChurnConfig(arrival_rate=3.0, mean_dwell=4.0, max_dwell=6), 10,
        rng)
    m = schedule.n_sessions
    assert m > 6 and m % 6
    names = np.asarray(sc.SCENARIOS)[np.arange(m) % len(sc.SCENARIOS)]
    sessions = sc.gen_episode_batch(names, schedule.max_dwell, rng,
                                    include_iq=False)
    c, params = tiny_ssm(seed=6)
    params = _in_range(params)
    prof = vgg_split_profile(FULL)
    kw = dict(churn=schedule, capacity=8, estimator=(c, params))
    if sched:
        kw.update(sched=SchedulerConfig(policy=sched), n_cells=2,
                  cell_idx=(np.arange(m) % 2).astype(np.int32))
    run = lambda: simulate_fleet(sessions, fig6_style_table(prof), prof,
                                 ControllerConfig(0.5, 2, 3), **kw)
    got = run()
    want_est = _manual_estimate(sessions, c, params, 6)
    assert ((want_est > 1.0) & (want_est < 130.0)).all()
    monkeypatch.setattr(engine, "estimate_fleet",
                        lambda ep, est, **_: want_est)
    want = run()
    for f in ("splits", "est_tp", "active", "prb_share", "delay_s"):
        x, y = getattr(got, f), getattr(want, f)
        if x is None:
            assert y is None, f
        else:
            np.testing.assert_array_equal(x, y, err_msg=f)
    assert got.active.any()


def test_step_scan_matches_sequence():
    """A scan of O(1) steps from the zero state == the chunked sequence
    pass: same forecasts, same final state (allclose; the chunked scan
    accumulates in a different order)."""
    c, params = tiny_ssm(forecast_horizon=3)
    rng = np.random.default_rng(3)
    feats = jnp.asarray(rng.normal(size=(4, 20, c.n_feats)), jnp.float32)
    fc_seq, s_seq = ssm_forward_seq(c, params, feats)
    state = ssm_state_init(c, (4,))
    fcs = []
    for t in range(20):
        state, fc_t = ssm_step(c, params, state, feats[:, t])
        fcs.append(np.asarray(fc_t))
    np.testing.assert_allclose(np.stack(fcs, 1), np.asarray(fc_seq),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s_seq),
                               atol=1e-5, rtol=1e-5)


def test_warm_state_then_steps_matches_full_sequence():
    """Warmup via ``ssm_warm_state`` + stepping the remainder == running
    the whole trace — the serving paths' split is seamless."""
    c, params = tiny_ssm(seed=1)
    rng = np.random.default_rng(4)
    feats = jnp.asarray(rng.normal(size=(3, 16, c.n_feats)), jnp.float32)
    _, s_full = ssm_forward_seq(c, params, feats)
    state = ssm_warm_state(c, params, feats[:, :10])
    for t in range(10, 16):
        state, _ = ssm_step(c, params, state, feats[:, t])
    np.testing.assert_allclose(np.asarray(state), np.asarray(s_full),
                               atol=1e-5, rtol=1e-5)


def test_forecast_horizon_zero_is_bit_identical_current_estimate():
    """The K=0 pin: a K>0 config's column 0 IS the K=0 forecast array,
    bit for bit, and ``reduce_forecasts`` at K=0 returns column 0
    unchanged under EVERY policy — forecasting never perturbs the
    1-step estimate."""
    c0, params = tiny_ssm()
    c4 = dataclasses.replace(c0, forecast_horizon=4,
                             forecast_policy="discount")
    rng = np.random.default_rng(5)
    feats = jnp.asarray(rng.normal(size=(4, 12, c0.n_feats)), jnp.float32)
    fc0, s0 = ssm_forward_seq(c0, params, feats)
    fc4, s4 = ssm_forward_seq(c4, params, feats)
    assert fc0.shape[-1] == 1 and fc4.shape[-1] == 5
    np.testing.assert_array_equal(np.asarray(fc4[..., 0]),
                                  np.asarray(fc0[..., 0]))
    np.testing.assert_array_equal(np.asarray(s4), np.asarray(s0))
    for policy in ("last", "min", "discount"):
        ck0 = dataclasses.replace(c0, forecast_policy=policy)
        np.testing.assert_array_equal(reduce_forecasts(ck0, np.asarray(fc0)),
                                      np.asarray(fc0)[..., 0])


def test_reduce_forecasts_policies():
    c, _ = tiny_ssm(forecast_horizon=2)
    fc = np.array([[3.0, 1.0, 5.0], [2.0, 2.0, 2.0]])
    last = reduce_forecasts(dataclasses.replace(c, forecast_policy="last"), fc)
    np.testing.assert_array_equal(last, [3.0, 2.0])
    mn = reduce_forecasts(dataclasses.replace(c, forecast_policy="min"), fc)
    np.testing.assert_array_equal(mn, [1.0, 2.0])
    d = dataclasses.replace(c, forecast_policy="discount",
                            forecast_discount=0.5)
    disc = reduce_forecasts(d, fc)
    w = np.array([1.0, 0.5, 0.25]) / 1.75
    np.testing.assert_allclose(disc, fc @ w, rtol=1e-6)
    # a convex combination: always within the forecast envelope
    assert (disc >= fc.min(-1) - 1e-9).all()
    assert (disc <= fc.max(-1) + 1e-9).all()


# ------------------------------------------------------- engine dispatch
def test_estimate_fleet_ssm_matches_manual_sequence():
    """The engine's recurrent arm == the manual composition: features ->
    one sequence pass -> the WINDOW-1 alignment slice -> policy reduce ->
    clip, bit for bit."""
    c, params = tiny_ssm(forecast_horizon=2, forecast_policy="min")
    ep = episode(6, T=8)
    got = estimate_fleet(ep, (c, params))
    feats = episode_features(ep.kpms, ep.alloc_ratio)
    fc, _ = ssm_forward_seq(c, params, jnp.asarray(feats))
    want = np.clip(reduce_forecasts(
        c, np.asarray(fc[:, WINDOW - 1:WINDOW - 1 + ep.n_steps])), 1.0, 130.0)
    np.testing.assert_array_equal(got, want)
    assert got.shape == (6, 8)


def test_estimate_fleet_forecast_policy_ordering():
    """Same params, same episode: the min policy can never exceed the
    last policy (clip is monotone), and discount stays within them and
    the envelope."""
    c, params = tiny_ssm(seed=2, forecast_horizon=3)
    ep = episode(6, T=8, seed=7)
    est = {p: estimate_fleet(
        ep, (dataclasses.replace(c, forecast_policy=p), params))
        for p in ("last", "min", "discount")}
    assert (est["min"] <= est["last"] + 1e-6).all()
    assert (est["min"] <= est["discount"] + 1e-6).all()
    # K=0 with any policy == the horizonless config
    e0 = estimate_fleet(ep, (dataclasses.replace(c, forecast_horizon=0,
                                                 forecast_policy="min"),
                             params))
    np.testing.assert_array_equal(
        e0, estimate_fleet(ep, (dataclasses.replace(c, forecast_horizon=0),
                                params)))


def test_ssm_refuses_windowed_only_switches():
    c, params = tiny_ssm()
    ep = episode(2, T=4)
    with pytest.raises(ValueError, match="int8 serving"):
        estimate_fleet(ep, (c, params), quant="int8")
    lean = sc.gen_episode_batch(["none", "cci"], 4,
                                np.random.default_rng(0), include_iq=False,
                                include_kpms=False)
    with pytest.raises(ValueError, match="include_kpms"):
        estimate_fleet(lean, (c, params))
    with pytest.raises(ValueError, match="ring_quant"):
        buffer_init(8, c, quant="int8")
    with pytest.raises(ValueError, match="include_kpms"):
        online_estimate_fleet(lean, (c, params), OnlineConfig())


@multi_device
def test_sharded_ssm_estimate_matches_unsharded():
    """The mesh-sharded per-period step program == the single-device
    sequence pass (allclose): same math, state sharded over batch."""
    c, params = tiny_ssm(forecast_horizon=2, forecast_policy="discount")
    ep = episode(8, T=6)
    ref = estimate_fleet(ep, (c, params))
    got = estimate_fleet(ep, (c, params), serving=make_serving_mesh("8x1"))
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- training
def test_train_ssm_reduces_loss_and_predict_aligns():
    c, _ = tiny_ssm()
    ep = episode(12, T=10, seed=11)
    data = {"feats": episode_features(ep.kpms, ep.alloc_ratio),
            "tp": np.asarray(ep.tp_mbps, np.float32)}
    params, hist, metrics = train_ssm(c, data, steps=200, batch=8,
                                      lr=3e-3, log_every=50, eval_data=data)
    assert hist[-1][1] < hist[0][1] * 0.8
    pred = ssm_predict(c, params, data)
    assert pred.shape == (12, 10)
    assert metrics is not None and np.isfinite(metrics[1])
    # tail alignment: the last label column reads sequence index S-2
    fc, _ = ssm_forward_seq(c, params, jnp.asarray(data["feats"][:4]))
    np.testing.assert_allclose(pred[:4, -1], np.asarray(fc[:, -2, 0]),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------- online loop
def test_online_ssm_adapts_and_beats_frozen():
    """The recurrent closed loop learns: forced triggers reduce the late
    RMSE below the frozen random-init estimator's, loss falls across
    bursts, and the per-period cost never re-reads history (the ring
    stores O(1) (state, report, label) events)."""
    c, params = tiny_ssm()
    ep = episode(16, T=16, seed=9)
    ocfg = OnlineConfig(capacity=256, batch=64, steps=10, lr=3e-3,
                        min_fill=16, seed=1,
                        drift=DriftConfig(calibrate_periods=2,
                                          threshold_mbps=0.0, patience=1,
                                          cooldown=1))
    frozen = estimate_fleet(ep, (c, params))
    est, stats = online_estimate_fleet(ep, (c, params), ocfg)
    assert stats.n_adaptations >= 3
    assert stats.train_steps == stats.n_adaptations * ocfg.steps
    assert stats.train_loss[-1] < stats.train_loss[0]
    tp = np.asarray(ep.tp_mbps, float)
    late = slice(ep.n_steps // 2, None)
    rmse_onl = float(np.sqrt(np.mean((est[:, late] - tp[:, late]) ** 2)))
    rmse_frz = float(np.sqrt(np.mean((frozen[:, late] - tp[:, late]) ** 2)))
    assert rmse_onl < rmse_frz


def test_online_ssm_no_trigger_matches_frozen():
    """Monitor never trips -> the per-step loop degenerates to the frozen
    sequence estimate (allclose; step vs chunked accumulation)."""
    c, params = tiny_ssm(forecast_horizon=2, forecast_policy="min")
    ep = episode(4, T=6)
    ocfg = OnlineConfig(capacity=32, batch=8, steps=2, min_fill=4,
                        drift=DriftConfig(calibrate_periods=1,
                                          threshold_mbps=1e9, patience=1))
    est, stats = online_estimate_fleet(ep, (c, params), ocfg)
    np.testing.assert_allclose(est, estimate_fleet(ep, (c, params)),
                               rtol=1e-4, atol=1e-4)
    assert stats.n_adaptations == 0 and stats.train_steps == 0


@multi_device
def test_online_ssm_sharded_matches_unsharded():
    c, params = tiny_ssm()
    ep = episode(8, T=6)
    ocfg = OnlineConfig(capacity=64, batch=16, steps=3, min_fill=8,
                        drift=DriftConfig(calibrate_periods=2,
                                          threshold_mbps=0.0, patience=1,
                                          cooldown=1))
    est_u, st_u = online_estimate_fleet(ep, (c, params), ocfg)
    est_s, st_s = online_estimate_fleet(ep, (c, params), ocfg,
                                        serving=make_serving_mesh("8x1"))
    np.testing.assert_allclose(est_s, est_u, rtol=1e-4, atol=1e-3)
    np.testing.assert_array_equal(st_s.adapted, st_u.adapted)
    assert st_s.n_adaptations == st_u.n_adaptations > 0


# ------------------------------------------------------------- slot pool
def test_pool_ssm_full_pool_matches_batch_engine():
    """Degenerate churn (all sessions at t=0, capacity = sessions) with
    the recurrent estimator == the batch engine: bit-identical splits
    and estimates — slot i is session i with age t == period t."""
    c, params = tiny_ssm(forecast_horizon=1, forecast_policy="min")
    n, T = 6, 8
    ep = episode(n, T=T, seed=13)
    prof = vgg_split_profile(FULL)
    table = fig6_style_table(prof)
    cfg = ControllerConfig(0.5, 2, 3)
    base = simulate_fleet(ep, table, prof, cfg, estimator=(c, params))
    pool = simulate_fleet(ep, table, prof, cfg, estimator=(c, params),
                          churn=_full_pool_schedule(n, T), capacity=n)
    assert pool.active.all()
    np.testing.assert_array_equal(base.splits, pool.splits)
    np.testing.assert_array_equal(base.est_tp, pool.est_tp)


def test_pool_ssm_online_composes():
    """The recurrent online arm drives the slot pool: per-slot states
    reset to the session's warm state on admit, masked ring ingestion,
    and the adaptation trace comes back."""
    rng = np.random.default_rng(19)
    T, capacity = 12, 6
    ccfg = sc.ChurnConfig(arrival_rate=2.0, mean_dwell=4.0, max_dwell=6)
    schedule = sc.make_churn_schedule(ccfg, T, rng)
    if schedule.n_sessions == 0:  # pragma: no cover - rate keeps M > 0
        pytest.skip("empty arrival realisation")
    names = np.asarray(sc.SCENARIOS)[
        np.arange(schedule.n_sessions) % len(sc.SCENARIOS)]
    sessions = sc.gen_episode_batch(names, schedule.max_dwell, rng,
                                    include_iq=False)
    c, params = tiny_ssm()
    prof = vgg_split_profile(FULL)
    table = fig6_style_table(prof)
    cfg = ControllerConfig(0.5, 2, 3)
    ocfg = OnlineConfig(capacity=64, batch=8, steps=2, min_fill=8,
                        drift=DriftConfig(calibrate_periods=2,
                                          threshold_mbps=0.0, patience=1,
                                          cooldown=1))
    res = simulate_fleet(sessions, table, prof, cfg, churn=schedule,
                         capacity=capacity, estimator=(c, params),
                         online=ocfg)
    assert res.online is not None and res.online.rmse.shape == (T,)
    assert res.online.n_adaptations > 0
    assert res.active.shape == (capacity, T)
    assert (res.est_tp[~res.active] == 0.0).all()
    assert (res.est_tp[res.active] >= 1.0).all()
    # the ring only ever ingested live-slot events
    assert res.online.buffer_fill <= min(64, int(res.active.sum()))
    # masked ingestion needs ring room for every slot
    with pytest.raises(ValueError, match="cover the pool"):
        simulate_fleet(sessions, table, prof, cfg, churn=schedule,
                       capacity=capacity, estimator=(c, params),
                       online=OnlineConfig(capacity=4))


# ----------------------------------------- PR 7 LSTM bit-identity pins
def test_lstm_plain_path_bit_identical():
    """The windowed estimator's batch path must BE the PR 7 program: the
    chunked multi-period forward == the per-period ``fwd`` loop, clipped,
    bit for bit (the SSM dispatch branch can never perturb it)."""
    e, params = tiny_lstm()
    ep = episode(5, T=7, iq=True)
    got = estimate_fleet(ep, (e, params))
    wins = ep.kpm_windows(normalize=True).astype(np.float32)
    alloc = jnp.asarray(ep.alloc_ratio, jnp.float32)
    want = np.empty((5, 7))
    for t in range(7):
        want[:, t] = np.asarray(fwd(
            e, params, jnp.asarray(wins[:, t]),
            jnp.asarray(ep.iq[:, t], jnp.float32), alloc))
    np.testing.assert_array_equal(got, np.clip(want, 1.0, 130.0))


def test_lstm_sched_path_bit_identical():
    """simulate_fleet(sched=...) with the LSTM == the manual
    estimate_fleet -> run_scheduled composition, bit for bit."""
    e, params = tiny_lstm()
    n, T, n_cells = 6, 7, 2
    ep = episode(n, T=T, iq=True, seed=21)
    prof = vgg_split_profile(FULL)
    table = fig6_style_table(prof)
    cfg = ControllerConfig(0.5, 2, 3)
    grid = np.repeat((np.arange(n) % n_cells)[:, None], T, axis=1)
    scfg = SchedulerConfig("pf", pf_beta=0.3)
    res = simulate_fleet(ep, table, prof, cfg, estimator=(e, params),
                         sched=scfg, cell_idx=grid, n_cells=n_cells)
    est = estimate_fleet(ep, (e, params))
    tables = np.broadcast_to(table.table, (n, len(table.table)))
    splits, shares = run_scheduled(tables, est, cfg, cfg.fallback_split,
                                   scfg, n_cells, grid,
                                   np.asarray(ep.tp_mbps, float))
    np.testing.assert_array_equal(res.splits, splits)
    np.testing.assert_array_equal(res.prb_share, shares)
    np.testing.assert_array_equal(res.est_tp, est * shares)


def test_lstm_churn_path_bit_identical():
    """Degenerate churn with the LSTM estimator == the batch engine:
    bit-identical splits and estimates."""
    e, params = tiny_lstm()
    n, T = 6, 8
    ep = episode(n, T=T, iq=True, seed=23)
    prof = vgg_split_profile(FULL)
    table = fig6_style_table(prof)
    cfg = ControllerConfig(0.5, 2, 3)
    base = simulate_fleet(ep, table, prof, cfg, estimator=(e, params))
    pool = simulate_fleet(ep, table, prof, cfg, estimator=(e, params),
                          churn=_full_pool_schedule(n, T), capacity=n)
    assert pool.active.all()
    np.testing.assert_array_equal(base.splits, pool.splits)
    np.testing.assert_array_equal(base.est_tp, pool.est_tp)


def test_lstm_online_loop_bit_identical():
    """The LSTM online loop == a test-local reimplementation from the
    public pieces (predict, ring, monitor, step program) under identical
    rng/key streams: same estimates and final params, bit for bit."""
    e, params0 = tiny_lstm()
    ep = episode(8, T=8, iq=True, seed=25)
    ocfg = OnlineConfig(capacity=64, batch=16, steps=3, min_fill=8, seed=4,
                        drift=DriftConfig(calibrate_periods=2,
                                          threshold_mbps=0.0, patience=1,
                                          cooldown=1))
    est, stats = online_estimate_fleet(ep, (e, params0), ocfg)
    assert stats.n_adaptations > 0  # the pin must cover adapted periods
    # --- reference loop, spelled out ---
    n, T = ep.n_ues, ep.n_steps
    wins = ep.kpm_windows(normalize=True).astype(np.float32)
    opt = AdamW(lr=ocfg.lr, weight_decay=ocfg.weight_decay,
                clip_norm=ocfg.clip_norm)
    params, opt_state = params0, opt.init(params0)
    step_fn = online_step_program(e, opt, None)
    buf = buffer_init(ocfg.capacity, e)
    dstate = drift_init()
    rng = np.random.default_rng(ocfg.seed)
    key = jax.random.PRNGKey(ocfg.seed)
    ref = np.empty((n, T))
    alloc_d = jnp.asarray(ep.alloc_ratio, jnp.float32)
    for t in range(T):
        s = emit_period_samples(ep, t, wins)
        kpms_t = jnp.asarray(s["kpms"])
        iq_t = jnp.asarray(s["iq"])
        ref[:, t] = np.clip(np.asarray(
            fwd(e, params, kpms_t, iq_t, alloc_d)), 1.0, 130.0)
        rmse_t = float(np.sqrt(np.mean((ref[:, t] - s["tp"]) ** 2)))
        buf = buffer_add(buf, kpms_t, iq_t, alloc_d,
                         jnp.asarray(s["tp"], jnp.float32))
        fill = buffer_count(buf)
        dstate, fired = drift_step(ocfg.drift, dstate, rmse_t,
                                   armed=fill >= ocfg.min_fill)
        if fired:
            data = buffer_data(buf)
            for _ in range(ocfg.steps):
                idx = jnp.asarray(rng.integers(0, fill, ocfg.batch), I32)
                key, sub = jax.random.split(key)
                params, opt_state, _ = step_fn(params, opt_state, data,
                                               idx, sub)
    np.testing.assert_array_equal(est, ref)
    for a, b in zip(jax.tree.leaves(stats.params), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
