"""Multi-cell layer tests: scheduler properties (PRB conservation,
no-starvation under proportional-fair, permutation-equivariance), the
1-cell/no-coupling equivalence regression against the PR-2 engine path,
load-coupled interference, and the cells orchestration. Property tests run
through hypothesis when available, otherwise a fixed-seed sweep of the
same checks (the suite's standard pattern)."""
try:
    import hypothesis
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - depends on environment
    HAVE_HYPOTHESIS = False

import numpy as np
import pytest

from repro.channel import scenarios as sc
from repro.channel import throughput as tpm
from repro.core.controller import ControllerConfig
from repro.core.energy import EDGE_A40X2, UE_VM_2CORE
from repro.core.objective import Constraints, Weights
from repro.core.pso import pso_vectorized
from repro.models.vgg import FULL, vgg_split_profile
from repro.sim import (POLICIES, SchedulerConfig, attach_ring,
                       build_cells_episode, cell_load,
                       coupled_interference_mw, handover_grid, jain_index,
                       ring_coupling, scheduler_init, scheduler_step,
                       simulate_cells, simulate_fleet)

N_CELLS = 3


def _random_fleet(rng, n):
    """Random cell assignment (every cell represented) + distinct rates."""
    cell_idx = np.concatenate([np.arange(N_CELLS),
                               rng.integers(0, N_CELLS, n - N_CELLS)])
    rate = rng.uniform(0.5, 130.0, n)
    return cell_idx.astype(np.int32), rate


def _run_steps(cfg, cell_idx, rates):
    """Advance the scheduler over the (T, N) rate rows; returns (T, N)
    shares and the final state."""
    state = scheduler_init(rates.shape[1])
    shares = []
    for r in rates:
        state, s = scheduler_step(cfg, N_CELLS, state, cell_idx, r)
        shares.append(np.asarray(s))
    return np.stack(shares), state


def _check_conservation(seed, policy):
    """Allocations sum to the cell budget (every period, every non-empty
    cell) and each share is a valid fraction."""
    rng = np.random.default_rng(seed)
    n = 17
    cell_idx, _ = _random_fleet(rng, n)
    rates = rng.uniform(0.5, 130.0, (6, n))
    cfg = SchedulerConfig(policy=policy, n_prb=100)
    shares, _ = _run_steps(cfg, cell_idx, rates)
    assert shares.min() >= 0.0 and shares.max() <= 1.0 + 1e-6
    for c in range(N_CELLS):
        alloc = (shares[:, cell_idx == c] * cfg.n_prb).sum(axis=1)
        np.testing.assert_allclose(alloc, cfg.n_prb, rtol=1e-5)


def _check_pf_no_starvation(seed):
    """Proportional-fair never starves: every UE's share is strictly
    positive every period, and a persistently weak UE's share *grows* as
    its served average decays."""
    rng = np.random.default_rng(seed)
    n = 12
    cell_idx, _ = _random_fleet(rng, n)
    rates = rng.uniform(20.0, 130.0, (25, n))
    rates[:, 0] = 1.0  # one persistently weak UE in cell 0
    cell_idx[0] = 0
    # ...which it shares with at least one other UE: alone, its share is
    # 1.0 every period and cannot grow (cells 1.. stay covered by 1..)
    cell_idx[N_CELLS] = 0
    cfg = SchedulerConfig(policy="pf")
    shares, _ = _run_steps(cfg, cell_idx, rates)
    assert np.all(shares > 0.0)
    served = (shares * rates).mean(axis=0)
    assert np.all(served > 0.0)
    # PF self-balancing: the weak UE's share rises from its cold start
    assert shares[-1, 0] > shares[0, 0]


def _check_equivariance(seed, policy):
    """Permuting the UE axis (assignment, rates, carried PF state) permutes
    the allocations — nothing in the scheduler depends on UE order."""
    rng = np.random.default_rng(seed)
    n = 14
    cell_idx, rate = _random_fleet(rng, n)
    state = scheduler_init(n)
    state = state._replace(avg_tp=state.avg_tp *
                           rng.uniform(0.5, 2.0, n).astype(np.float32))
    cfg = SchedulerConfig(policy=policy)
    perm = rng.permutation(n)
    s1, share1 = scheduler_step(cfg, N_CELLS, state, cell_idx, rate)
    s2, share2 = scheduler_step(
        cfg, N_CELLS, state._replace(avg_tp=state.avg_tp[perm]),
        cell_idx[perm], rate[perm])
    np.testing.assert_allclose(np.asarray(share2),
                               np.asarray(share1)[perm], rtol=1e-5)
    np.testing.assert_allclose(np.asarray(s2.avg_tp),
                               np.asarray(s1.avg_tp)[perm], rtol=1e-5)


if HAVE_HYPOTHESIS:
    @hypothesis.settings(max_examples=15, deadline=None)
    @hypothesis.given(seed=st.integers(0, 10_000),
                      policy=st.sampled_from(POLICIES))
    def test_prb_conservation(seed, policy):
        _check_conservation(seed, policy)

    @hypothesis.settings(max_examples=10, deadline=None)
    @hypothesis.given(seed=st.integers(0, 10_000))
    def test_pf_no_starvation(seed):
        _check_pf_no_starvation(seed)

    @hypothesis.settings(max_examples=15, deadline=None)
    @hypothesis.given(seed=st.integers(0, 10_000),
                      policy=st.sampled_from(POLICIES))
    def test_permutation_equivariance(seed, policy):
        _check_equivariance(seed, policy)
else:
    @pytest.mark.parametrize("seed", range(4))
    @pytest.mark.parametrize("policy", POLICIES)
    def test_prb_conservation(seed, policy):
        _check_conservation(seed, policy)

    @pytest.mark.parametrize("seed", range(6))
    def test_pf_no_starvation(seed):
        _check_pf_no_starvation(seed)

    @pytest.mark.parametrize("seed", range(4))
    @pytest.mark.parametrize("policy", POLICIES)
    def test_permutation_equivariance(seed, policy):
        _check_equivariance(seed, policy)


def test_policy_shapes():
    """rr is an equal time-share; maxsinr hands each cell's whole budget
    to its top-rate UE; pf sits strictly between at the cold start."""
    rng = np.random.default_rng(0)
    n = 9
    cell_idx = np.repeat(np.arange(N_CELLS), 3).astype(np.int32)
    rate = rng.uniform(1.0, 130.0, n)
    state = scheduler_init(n)
    _, rr = scheduler_step(SchedulerConfig("rr"), N_CELLS, state, cell_idx,
                           rate)
    _, mx = scheduler_step(SchedulerConfig("maxsinr"), N_CELLS, state,
                           cell_idx, rate)
    np.testing.assert_allclose(np.asarray(rr), 1.0 / 3.0, rtol=1e-6)
    mx = np.asarray(mx)
    for c in range(N_CELLS):
        m = cell_idx == c
        assert mx[m][np.argmax(rate[m])] == pytest.approx(1.0)
        assert np.count_nonzero(mx[m]) == 1  # distinct rates: one winner
    assert jain_index(np.asarray(rr)) > jain_index(mx)


def test_unknown_policy_rejected():
    # ValueError, not assert: config validation must survive python -O
    with pytest.raises(ValueError, match="unknown policy"):
        SchedulerConfig(policy="edf")
    with pytest.raises(ValueError, match="n_prb"):
        SchedulerConfig(n_prb=0)
    with pytest.raises(ValueError, match="pf_beta"):
        SchedulerConfig(pf_beta=1.5)


# ------------------------------------------------------- coupling layer
def test_cell_load_mean_of_attached():
    grid = np.array([[0, 0], [0, 1], [1, 1]])  # (N=3, T=2)
    demand = np.array([0.2, 0.4, 0.8])
    load = cell_load(grid, demand, n_cells=3)
    np.testing.assert_allclose(load, [[0.3, 0.2], [0.8, 0.6], [0.0, 0.0]])


def test_coupled_interference_mw_hand_computed():
    grid = np.array([[0, 0], [1, 1]])
    demand = np.array([1.0, 0.5])
    coupling = np.array([[0.0, 2.0], [4.0, 0.0]])
    extra = coupled_interference_mw(grid, demand, coupling)
    # UE0 (cell 0) sees 2.0 * load(cell1)=0.5 -> 1.0 mW; UE1 sees 4.0 * 1.0
    np.testing.assert_allclose(extra, [[1.0, 1.0], [4.0, 4.0]])


def test_ring_coupling_structure():
    c = ring_coupling(4, neighbor_dbm=-12.0, decay=0.5)
    assert np.all(np.diag(c) == 0.0)
    np.testing.assert_allclose(c[0, 1], 10 ** (-1.2))
    np.testing.assert_allclose(c[0, 2], 10 ** (-1.2) * 0.5)  # two hops
    np.testing.assert_allclose(c, c.T)


def test_coupling_raises_interference_floor_and_lowers_labels():
    """Neighbour-cell load must raise even a quiet (S0) UE's interference
    floor and depress its ground-truth throughput label."""
    n, T = 8, 6
    cell0 = attach_ring(n, 2)
    grid = np.repeat(cell0[:, None], T + sc.WINDOW, axis=1)
    scen = np.array(["none"] * n)
    loads = np.full(n, 0.9)
    off = build_cells_episode(scen, T, np.random.default_rng(9), grid, None,
                              load_ratio=loads)
    on = build_cells_episode(scen, T, np.random.default_rng(9), grid,
                             ring_coupling(2, neighbor_dbm=-5.0),
                             load_ratio=loads)
    assert np.all(off.int_dbm == -60.0)
    assert np.all(on.int_dbm > -60.0)
    assert on.tp_mbps.mean() < off.tp_mbps.mean()


def test_power_sum_dbm_linear_power():
    base = np.array([-60.0, 0.0])
    extra = np.array([10 ** (-6.0), 1.0])
    got = sc.power_sum_dbm(base, extra)
    want = 10 * np.log10(10 ** (base / 10) + extra)
    np.testing.assert_allclose(got, want)
    assert sc.power_sum_dbm(np.array([14.0]), np.array([1e3]))[0] == 14.0


def test_prb_scaled_throughput():
    tp = np.array([100.0, 50.0, 8.0])
    np.testing.assert_allclose(tpm.prb_scaled_mbps(tp, [0.5, 1.0, 0.0]),
                               [50.0, 50.0, tpm.PRB_FLOOR_MBPS])
    got = tpm.shared_throughput_mbps(np.array([-60.0]), 0.25)
    np.testing.assert_allclose(got,
                               tpm.max_throughput_mbps(np.array([-60.0]))
                               * 0.25)


# ------------------------------------------------- equivalence regression
def _fig6_like_setup():
    prof = vgg_split_profile(FULL)
    cons = Constraints(rho_max=0.92, tau_max_s=6.0, e_max_j=40.0)
    table = pso_vectorized(prof, UE_VM_2CORE, EDGE_A40X2,
                           Weights(1.0, 0.15, 0.1), cons, 130)
    fixed = int(table.query(130.0))
    cfg = ControllerConfig(ewma_alpha=0.6, hysteresis_steps=2,
                           fallback_split=fixed)
    return prof, table, cfg, fixed


def test_one_cell_no_coupling_matches_engine_exactly():
    """The satellite regression: a 1-cell, coupling-off, scheduler-off
    cells fleet must reproduce ``simulate_fleet`` bit-for-bit (splits) and
    float-identically (metrics) — the scheduler hook is a no-op by
    default."""
    prof, table, cfg, fixed = _fig6_like_setup()
    n, T = 12, 10
    scen = np.asarray(sc.SCENARIOS)[np.arange(n) % 4]
    grid = np.zeros((n, T + sc.WINDOW), int)
    ep = build_cells_episode(scen, T, np.random.default_rng(11), grid, None)
    base = simulate_fleet(ep, table, prof, cfg, fixed_split=fixed)
    cell = simulate_cells(ep, grid, table, prof, cfg, sched=None,
                          fixed_split=fixed)
    assert cell.fleet.prb_share is None
    np.testing.assert_array_equal(cell.fleet.splits, base.splits)
    for f in ("est_tp", "delay_s", "privacy", "energy_j"):
        np.testing.assert_array_equal(getattr(cell.fleet, f),
                                      getattr(base, f))
        np.testing.assert_array_equal(getattr(cell.fleet.fixed, f),
                                      getattr(base.fixed, f))


def test_one_ue_per_cell_rr_matches_no_scheduler():
    """With one UE per cell every policy grants the full budget (share ==
    1.0 exactly), so the scheduled scan must reproduce the unscheduled
    engine bit-for-bit — pinning that the hook itself adds no drift."""
    prof, table, cfg, fixed = _fig6_like_setup()
    n, T = 6, 10
    scen = np.asarray(sc.SCENARIOS)[np.arange(n) % 4]
    grid = np.repeat(np.arange(n)[:, None], T + sc.WINDOW, axis=1)
    ep = build_cells_episode(scen, T, np.random.default_rng(13), grid, None)
    base = simulate_fleet(ep, table, prof, cfg, fixed_split=fixed)
    cell = simulate_cells(ep, grid, table, prof, cfg,
                          sched=SchedulerConfig(policy="rr"),
                          fixed_split=fixed)
    np.testing.assert_array_equal(cell.fleet.prb_share, 1.0)
    np.testing.assert_array_equal(cell.fleet.splits, base.splits)
    for f in ("delay_s", "privacy", "energy_j"):
        np.testing.assert_array_equal(getattr(cell.fleet, f),
                                      getattr(base, f))


# ----------------------------------------------------------- integration
def test_simulate_cells_contended_with_handover():
    """Full stack: coupling + cell handover + scheduler. Shares stay
    conserved per cell each period, contention depresses served throughput
    below the full-grant truth, and maxsinr is measurably less fair."""
    prof, table, cfg, fixed = _fig6_like_setup()
    rng = np.random.default_rng(17)
    n, T, C = 24, 12, 3
    scen = np.asarray(sc.SCENARIOS)[np.arange(n) % 4]
    grid = handover_grid(attach_ring(n, C), T + sc.WINDOW, 0.25, rng)
    ep = build_cells_episode(scen, T, rng, grid, ring_coupling(C))
    results = {}
    for pol in POLICIES:
        res = simulate_cells(ep, grid, table, prof, cfg,
                             sched=SchedulerConfig(policy=pol),
                             fixed_split=fixed)
        np.testing.assert_allclose(res.share_sums(), 1.0, rtol=1e-5)
        assert res.fleet.prb_share.shape == (n, T)
        assert np.all(res.served_mbps <= res.fleet.true_tp + 1e-9)
        results[pol] = res
    assert results["maxsinr"].jain() < results["rr"].jain()
    # the handover lands inside the report window the scheduler scans,
    # not in the KPM warm-up prefix
    rep = results["rr"].cell_idx
    assert np.any(rep[:, 0] != rep[:, -1])


def test_handover_grid_explicit_n_cells_and_warmup_default():
    """The ring modulus must come from the topology, not the occupied
    cells, and the default handover step must land past the warm-up."""
    rng = np.random.default_rng(1)
    cell0 = attach_ring(3, 4)  # cells {0,1,2} occupied, ring has 4
    grid = handover_grid(cell0, 8 + sc.WINDOW, 1.0, rng, n_cells=4)
    assert grid.max() == 3  # the UE in cell 2 wraps to cell 3, not cell 0
    changed = np.flatnonzero(grid[0] != grid[0, 0])
    assert changed.min() >= sc.WINDOW  # default t_h past the warm-up


def test_share_sums_reports_one_for_empty_cells():
    """An empty cell has no budget to conserve: share_sums() must compare
    clean against 1.0 even when a cell is unoccupied for some periods."""
    prof, table, cfg, fixed = _fig6_like_setup()
    n, T = 4, 6
    scen = np.array(["cci"] * n)
    # cell 2 of 3 never has an attached UE
    grid = np.repeat(np.array([0, 0, 1, 1])[:, None], T + sc.WINDOW, axis=1)
    ep = build_cells_episode(scen, T, np.random.default_rng(2), grid, None)
    res = simulate_cells(ep, grid, table, prof, cfg, n_cells=3,
                         sched=SchedulerConfig(policy="rr"))
    assert res.n_cells == 3
    np.testing.assert_allclose(res.share_sums(), 1.0, rtol=1e-5)
