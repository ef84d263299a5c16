"""Fleet engine tests: functional controller == sequential class (property
test under vmap+scan), StackedLookupTable.query_many == looped query,
batched episode generation, and engine-vs-looped equivalence. Property
tests run through hypothesis when available, otherwise a fixed-seed sweep
of the same checks."""
try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - depends on environment
    HAVE_HYPOTHESIS = False

import numpy as np
import pytest

from repro.channel import scenarios as sc
from repro.core.controller import (AdaptiveSplitController, ControllerConfig,
                                   NO_SPLIT, PENDING_NONE, controller_init,
                                   controller_step)
from repro.core.energy import EDGE_A40X2, UE_VM_2CORE
from repro.core.objective import Constraints, Weights
from repro.core.pso import LookupTable, StackedLookupTable, pso_vectorized
from repro.models.vgg import FULL, vgg_split_profile
from repro.sim import run_controllers, simulate_fleet, simulate_fleet_looped

N_SC_TEST = 16


def random_stacked(rng, n_ues, width=40, n_splits=12) -> StackedLookupTable:
    """Random lookup rows: bucket 0 always NO_SPLIT (the sweep starts at
    1 Mbps), other buckets may be NO_SPLIT or any split index."""
    tables = rng.integers(-1, n_splits, (n_ues, width + 1)).astype(np.int32)
    tables[:, 0] = NO_SPLIT
    return StackedLookupTable(
        ue_names=[f"ue{i}" for i in range(n_ues)], tables=tables,
        tp_min_mbps=np.zeros((n_ues, n_splits)),
        feasible_prefilter=np.ones((n_ues, n_splits), bool))


def reference_update(table, cfg, state, tp):
    """The original stateful-class update logic, transcribed with float32
    EWMA arithmetic (what the functional core uses). ``state`` is the dict
    (ewma|None, current, pending|None, count)."""
    a = np.float32(cfg.ewma_alpha)
    tp = np.float32(tp)
    ewma = (tp if state["ewma"] is None
            else np.float32(a * tp + np.float32(1.0 - cfg.ewma_alpha)
                            * state["ewma"]))
    state["ewma"] = ewma
    bucket = int(np.clip(np.round(ewma), 0, len(table) - 1))
    proposal = int(table[bucket])
    if proposal == NO_SPLIT:
        proposal = cfg.fallback_split
    if proposal != state["current"]:
        if proposal == state["pending"]:
            state["count"] += 1
        else:
            state["pending"], state["count"] = proposal, 1
        if state["count"] >= cfg.hysteresis_steps:
            state["current"] = proposal
            state["pending"], state["count"] = None, 0
    else:
        state["pending"], state["count"] = None, 0
    return state["current"]


def _check_batched_matches_sequential(seed, alpha, hysteresis, fallback):
    """vmap+scan over the fleet == per-UE sequential class == the original
    class logic, step for step."""
    rng = np.random.default_rng(seed)
    n_ues, t_steps = 5, 40
    stacked = random_stacked(rng, n_ues)
    cfg = ControllerConfig(ewma_alpha=alpha, hysteresis_steps=hysteresis,
                           fallback_split=fallback)
    tps = rng.uniform(0.0, stacked.tables.shape[1] + 5.0, (n_ues, t_steps))
    batched = run_controllers(stacked.tables, tps, cfg, NO_SPLIT)
    for u in range(n_ues):
        ctl = AdaptiveSplitController(stacked.row(u), cfg)
        ref = {"ewma": None, "current": NO_SPLIT, "pending": None, "count": 0}
        for t in range(t_steps):
            got = ctl.update(float(tps[u, t]))
            want = reference_update(stacked.tables[u], cfg, ref,
                                    float(tps[u, t]))
            assert got == want == batched[u, t], (u, t, got, want,
                                                  batched[u, t])
        # internal hysteresis state must agree too, not just the output
        assert (ctl.pending_split is None) == (ref["pending"] is None)
        if ref["pending"] is not None:
            assert ctl.pending_split == ref["pending"]
        assert ctl.pending_count == ref["count"]


def _check_query_many_matches_query(seed):
    rng = np.random.default_rng(seed)
    stacked = random_stacked(rng, 7)
    tps = rng.uniform(-1.0, stacked.tables.shape[1] + 10.0, 7)
    tps[0] = 0.2  # 0-bucket NO_SPLIT edge: must not clamp up to bucket 1
    got = stacked.query_many(tps)
    want = [stacked.row(u).query(float(tps[u])) for u in range(7)]
    np.testing.assert_array_equal(got, want)
    assert got[0] == NO_SPLIT


if HAVE_HYPOTHESIS:
    @hypothesis.settings(max_examples=15, deadline=None)
    @hypothesis.given(seed=st.integers(0, 10_000), alpha=st.floats(0.05, 1.0),
                      hysteresis=st.integers(1, 4),
                      fallback=st.integers(-1, 11))
    def test_batched_controller_matches_sequential(seed, alpha, hysteresis,
                                                   fallback):
        _check_batched_matches_sequential(seed, alpha, hysteresis, fallback)

    @hypothesis.settings(max_examples=15, deadline=None)
    @hypothesis.given(seed=st.integers(0, 10_000))
    def test_query_many_matches_query(seed):
        _check_query_many_matches_query(seed)
else:
    @pytest.mark.parametrize("seed,alpha,hysteresis,fallback", [
        (0, 0.5, 2, -1), (1, 1.0, 1, 0), (2, 0.6, 2, 5), (3, 0.05, 3, -1),
        (4, 0.9, 4, 11), (5, 0.3, 2, 2), (6, 0.75, 1, -1), (7, 0.6, 3, 7),
    ])
    def test_batched_controller_matches_sequential(seed, alpha, hysteresis,
                                                   fallback):
        _check_batched_matches_sequential(seed, alpha, hysteresis, fallback)

    @pytest.mark.parametrize("seed", range(8))
    def test_query_many_matches_query(seed):
        _check_query_many_matches_query(seed)


def test_controller_reset_and_warm_start():
    rng = np.random.default_rng(0)
    stacked = random_stacked(rng, 1)
    ctl = AdaptiveSplitController(stacked.row(0), ControllerConfig(
        ewma_alpha=1.0, hysteresis_steps=1))
    ctl.update(20)
    assert ctl.switches and ctl.tp_ewma is not None
    ctl.reset(warm_split=9)
    assert ctl.current_split == 9
    assert ctl.tp_ewma is None and ctl.switches == []
    assert ctl.pending_split is None and ctl.pending_count == 0
    state = controller_init(warm_split=4, batch_shape=(3,))
    assert state.current_split.shape == (3,)
    assert int(state.pending_split[0]) == PENDING_NONE


def test_controller_step_single_matches_class():
    """Scalar controller_step drives the class: one more direct pin."""
    rng = np.random.default_rng(3)
    stacked = random_stacked(rng, 1)
    cfg = ControllerConfig(ewma_alpha=0.6, hysteresis_steps=2,
                           fallback_split=3)
    ctl = AdaptiveSplitController(stacked.row(0), cfg)
    state = controller_init()
    for tp in rng.uniform(0, 45, 25):
        state, split = controller_step(stacked.tables[0], state, float(tp),
                                       cfg=cfg)
        assert int(split) == ctl.update(float(tp))
    assert int(state.step) == 25


def test_stack_rejects_mixed_tp_max():
    a = LookupTable("a", np.full(11, NO_SPLIT, np.int32), np.zeros(3),
                    np.ones(3, bool))
    b = LookupTable("b", np.full(21, NO_SPLIT, np.int32), np.zeros(3),
                    np.ones(3, bool))
    with pytest.raises(AssertionError, match="mixed tp_max"):
        StackedLookupTable.stack([a, b])
    st2 = StackedLookupTable.stack([a, a])
    assert st2.n_ues == 2 and st2.row(1).ue_name == "a"


# --------------------------------------------------------------- episodes
def test_gen_episode_batch_shapes_and_labels():
    rng = np.random.default_rng(1)
    scen = np.array(["none", "jamming", "cci", "tdd", "jamming"])
    ep = sc.gen_episode_batch(scen, 6, rng, n_sc=N_SC_TEST)
    assert ep.n_ues == 5 and ep.n_steps == 6
    assert ep.int_dbm.shape == (5, 6 + sc.WINDOW)
    assert ep.kpms.shape == (5, 6 + sc.WINDOW, 15)
    assert ep.iq.shape == (5, 6, 2, N_SC_TEST, 14)
    assert ep.kpm_windows().shape == (5, 6, sc.WINDOW, 15)
    # labels are the ground-truth curve evaluated on the trace
    from repro.channel import throughput as tp
    np.testing.assert_allclose(
        ep.tp_mbps, tp.max_throughput_mbps(ep.int_dbm[:, sc.WINDOW:]))
    # the 'none' row is pinned at the interference floor
    assert np.all(ep.int_dbm[0] == -60.0)
    np.testing.assert_array_equal(ep.scenario_idx, [0, 1, 2, 3, 1])


def test_kpm_windows_match_sample_windows():
    """The strided window view must reproduce the per-sample window slices
    the sequential path hands the estimator."""
    rng = np.random.default_rng(2)
    ep = sc.gen_episode_batch(np.array(["cci"]), 5, rng, n_sc=N_SC_TEST)
    wins = ep.kpm_windows(normalize=False)
    for t in range(5):
        np.testing.assert_array_equal(
            wins[0, t], ep.kpms[0, t:t + sc.WINDOW])


def test_gen_episode_shim_matches_batch_layout():
    rng = np.random.default_rng(3)
    eps = sc.gen_episode("tdd", 4, rng, n_sc=N_SC_TEST)
    assert len(eps) == 4
    assert eps[0].kpms.shape == (sc.WINDOW, 15)
    assert eps[0].iq.shape == (2, N_SC_TEST, 14)
    assert eps[0].scenario == "tdd"


def test_gen_episode_batch_handover_grid():
    """Mid-episode scenario handover: per-step scenario grid changes the
    interference trace and KPM overlap after the handover point."""
    rng = np.random.default_rng(4)
    T, t_h = 8, sc.WINDOW + 4
    grid = np.full((3, T + sc.WINDOW), "none", dtype=object)
    grid[1:, t_h:] = "jamming"
    ep = sc.gen_episode_batch(grid, T, rng, load_ratio=0.5, n_sc=N_SC_TEST,
                              include_iq=False)
    assert ep.iq is None
    # pre-handover everything sits at the floor; post-handover rows 1-2
    # carry jamming interference while row 0 stays quiet
    assert np.all(ep.int_dbm[:, :t_h] == -60.0)
    assert np.all(ep.int_dbm[0] == -60.0)
    assert ep.int_dbm[1:, t_h:].max() > -60.0


def test_gen_dataset_balanced_and_shuffled():
    rng = np.random.default_rng(5)
    ds = sc.gen_dataset(25, rng, episode_len=10, n_sc=N_SC_TEST)
    counts = np.bincount(ds["scenario"], minlength=4)
    assert np.all(counts >= 25)
    assert ds["kpms"].shape == (counts.sum(), sc.WINDOW, 15)
    assert ds["iq"].dtype == np.float32
    # shuffled: scenarios must not come out in generation order
    assert len(np.unique(ds["scenario"][:10])) > 1


# --------------------------------------------------------------- engine
def test_simulate_fleet_matches_looped_mixed_fleet():
    """Vectorized engine == legacy loop on a mixed-scenario, heterogeneous
    fleet (bit-identical splits, float-identical metrics)."""
    rng = np.random.default_rng(6)
    prof = vgg_split_profile(FULL)
    cons = Constraints(rho_max=0.92, tau_max_s=6.0, e_max_j=40.0)
    table = pso_vectorized(prof, UE_VM_2CORE, EDGE_A40X2,
                           Weights(1.0, 0.15, 0.1), cons, 130)
    scen = np.asarray(sc.SCENARIOS)[np.arange(8) % 4]
    ep = sc.gen_episode_batch(scen, 10, rng, include_iq=False)
    cfg = ControllerConfig(ewma_alpha=0.6, hysteresis_steps=2,
                           fallback_split=int(table.query(130.0)))
    fixed = int(table.query(130.0))
    vec = simulate_fleet(ep, table, prof, cfg, fixed_split=fixed)
    loop = simulate_fleet_looped(ep, table, prof, cfg, fixed_split=fixed)
    np.testing.assert_array_equal(vec.splits, loop.splits)
    for f in ("delay_s", "privacy", "energy_j"):
        np.testing.assert_allclose(getattr(vec, f), getattr(loop, f),
                                   rtol=1e-12)
        np.testing.assert_allclose(getattr(vec.fixed, f),
                                   getattr(loop.fixed, f), rtol=1e-12)
    means = vec.scenario_means(ep.scenario_idx)
    assert set(means) == set(sc.SCENARIOS)


def test_simulate_fleet_stacked_tables_per_ue():
    """Per-UE tables: a fleet where half the UEs run a privacy-tightened
    table must take different decisions from the shared-table half."""
    rng = np.random.default_rng(7)
    prof = vgg_split_profile(FULL)
    loose = pso_vectorized(prof, UE_VM_2CORE, EDGE_A40X2,
                           Weights(1.0, 0.0, 0.0),
                           Constraints(rho_max=0.98), 60)
    tight = pso_vectorized(prof, UE_VM_2CORE, EDGE_A40X2,
                           Weights(1.0, 0.0, 0.0),
                           Constraints(rho_max=0.5), 60)
    assert not np.array_equal(loose.table, tight.table)
    stacked = StackedLookupTable.stack([loose, tight, loose, tight])
    ep = sc.gen_episode_batch(np.array(["cci"] * 4), 8, rng,
                              load_ratio=0.9, include_iq=False)
    # identical traces for all four UEs: decisions differ only via tables
    tr = np.tile(ep.int_dbm[:1], (4, 1))
    ep2 = sc.gen_episode_batch(np.array(["cci"] * 4), 8, rng,
                               load_ratio=0.9, include_iq=False, int_dbm=tr)
    cfg = ControllerConfig(ewma_alpha=1.0, hysteresis_steps=1,
                           fallback_split=0)
    res = simulate_fleet(ep2, stacked, prof, cfg)
    np.testing.assert_array_equal(res.splits[0], res.splits[2])
    np.testing.assert_array_equal(res.splits[1], res.splits[3])
    assert not np.array_equal(res.splits[0], res.splits[1])


def test_tp_clip_single_source():
    """The estimator clamp range is owned by the PSO sweep config; the sim
    engine must import it, not re-declare it (the two stay equal by
    construction)."""
    from repro.channel import throughput as tpm
    from repro.core import pso
    from repro.sim import engine
    import repro.sim as sim
    assert engine.TP_CLIP_MBPS is pso.TP_CLIP_MBPS
    assert sim.TP_CLIP_MBPS is pso.TP_CLIP_MBPS
    # and the range itself matches the sweep: bucket 1 .. the paper's peak
    assert pso.TP_CLIP_MBPS == (1.0, tpm.PEAK_MBPS)


def test_estimate_fleet_shapes_and_clip():
    """Batched estimator inference: (N, T) predictions, clipped into the
    PSO sweep range."""
    jax = pytest.importorskip("jax")
    from repro.estimator.model import EstimatorConfig, init_estimator
    from repro.sim import TP_CLIP_MBPS, estimate_fleet
    rng = np.random.default_rng(8)
    e = EstimatorConfig(n_sc=N_SC_TEST, lstm_hidden=8, hidden=8)
    params = init_estimator(e, jax.random.PRNGKey(0))
    ep = sc.gen_episode_batch(np.array(["none", "jamming"]), 3, rng,
                              n_sc=N_SC_TEST)
    est = estimate_fleet(ep, (e, params))
    assert est.shape == (2, 3)
    assert est.min() >= TP_CLIP_MBPS[0] and est.max() <= TP_CLIP_MBPS[1]


def _per_period_reference(ep, e, params):
    """The pre-vectorization loop: one estimator forward per period."""
    from repro.estimator.train import predict
    from repro.sim import TP_CLIP_MBPS
    wins = ep.kpm_windows(normalize=True).astype(np.float32)
    alloc = ep.alloc_ratio.astype(np.float32)
    ref = np.empty((ep.n_ues, ep.n_steps))
    for t in range(ep.n_steps):
        data = {"kpms": wins[:, t], "iq": ep.iq[:, t].astype(np.float32),
                "alloc": alloc, "tp": np.empty(ep.n_ues, np.float32)}
        ref[:, t] = np.asarray(predict(e, params, data, batch=None))
    return np.clip(ref, TP_CLIP_MBPS[0], TP_CLIP_MBPS[1])


def _chunk_episode(n=3, T=7):
    jax = pytest.importorskip("jax")
    from repro.estimator.model import EstimatorConfig, init_estimator
    rng = np.random.default_rng(9)
    e = EstimatorConfig(n_sc=N_SC_TEST, lstm_hidden=8, hidden=8)
    params = init_estimator(e, jax.random.PRNGKey(1))
    scen = np.asarray(sc.SCENARIOS)[np.arange(n) % 4]
    ep = sc.gen_episode_batch(scen, T, rng, n_sc=N_SC_TEST)
    return e, params, ep


def test_estimate_fleet_vectorized_matches_per_period_loop():
    """The period-chunked forward (many whole report periods flattened
    into one dispatch) must reproduce the old one-forward-per-period loop:
    the estimator is row-wise, so only the batch packing changed."""
    from repro.sim import estimate_fleet
    from repro.sim.engine import est_chunk_rows
    e, params, ep = _chunk_episode()
    assert ep.n_ues * ep.n_steps <= est_chunk_rows(e)  # one chunk
    est = estimate_fleet(ep, (e, params))
    np.testing.assert_allclose(est, _per_period_reference(ep, e, params),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("max_rows", [2, 7])
def test_estimate_fleet_small_chunks_match_per_period_loop(monkeypatch,
                                                           max_rows, fused):
    """A byte budget smaller than the fleet splits the UE axis as well as
    the period axis (2 rows: UE blocks of 2 + 1, one period each; 7 rows:
    all 3 UEs, 2 periods, a 1-period tail). Every split reproduces the
    per-period loop."""
    from repro.sim import engine, estimate_fleet
    e, params, ep = _chunk_episode()
    monkeypatch.setattr(engine, "EST_CHUNK_BYTES",
                        max_rows * e.row_bytes)
    assert engine.est_chunk_rows(e) == max_rows
    est = estimate_fleet(ep, (e, params), fused=fused)
    np.testing.assert_allclose(est, _per_period_reference(ep, e, params),
                               rtol=1e-5, atol=1e-5)


def test_est_chunk_rows_fit_byte_budget_at_paper_width():
    """At the paper's estimator width (n_sc=3276) one chunk's IQ input and
    conv1 activations — shapes taken from the traced conv, not from the
    row-bytes formula — stay under EST_CHUNK_BYTES (1 GiB), a sixteenth
    of one v5e's 16 GB HBM; the old fixed 8192 rows needed ~27 GB."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from jax import lax

    from repro.estimator.model import EstimatorConfig
    from repro.sim.engine import EST_CHUNK_BYTES, est_chunk_rows
    e = EstimatorConfig()
    rows = est_chunk_rows(e)
    assert EST_CHUNK_BYTES == 1 << 30
    assert 100 <= rows < 8192
    iq = jax.ShapeDtypeStruct((rows, e.n_sc, e.n_sym, 2), jnp.float32)
    w1 = jax.ShapeDtypeStruct((3, 3, 2, e.cnn_ch[0]), jnp.float32)
    conv1 = jax.eval_shape(lambda x, w: lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC")),
        iq, w1)
    held = 4 * (np.prod(iq.shape) + np.prod(conv1.shape))
    assert held <= EST_CHUNK_BYTES
    assert 4 * 8192 * (np.prod(iq.shape[1:]) + np.prod(conv1.shape[1:])) \
        > 16e9


def test_split_metrics_zero_throughput_finite():
    """Zero / near-zero throughput (an empty slot, a starved PRB grant)
    must yield huge-but-finite delay, never inf/NaN — and the floor must
    be invisible at any real operating point (>= the 0.01 Mbps PRB
    floor)."""
    from repro.sim import split_metrics
    from repro.sim.engine import TP_FLOOR_BPS
    prof = vgg_split_profile(FULL)
    splits = np.arange(len(prof.data_bytes))[None]
    zero = np.zeros_like(splits, float)
    delay, priv, energy = split_metrics(prof, splits, zero)
    assert np.isfinite(delay).all() and (delay > 0).all()
    assert np.isfinite(priv).all() and np.isfinite(energy).all()
    # the floored delay is exactly the transfer at TP_FLOOR_BPS
    expect = (prof.d_ue(UE_VM_2CORE)[splits] + prof.d_ser(EDGE_A40X2)[splits]
              + prof.data_bytes[splits] * 8.0 / TP_FLOOR_BPS)
    np.testing.assert_array_equal(delay, expect)
    # bit-unchanged for any live throughput: the smallest rate the PRB
    # scheduler can grant (0.01 Mbps = 1e4 bps) is far above the floor
    tp = np.full_like(splits, 0.01, dtype=float)
    d_floor, _, _ = split_metrics(prof, splits, tp)
    ref = (prof.d_ue(UE_VM_2CORE)[splits] + prof.d_ser(EDGE_A40X2)[splits]
           + prof.data_bytes[splits] * 8.0 / (0.01 * 1e6))
    np.testing.assert_array_equal(d_floor, ref)
