"""The RAN simulator that feeds the benchmark: interference scenarios S0-S3,
0.1 s KPM reports, uplink IQ spectrograms, the ground-truth throughput
label, the churned session process and the ring of coupled cells.

A copy of the program's channel models (``repro.channel.scenarios``,
``kpm``, ``iq``, ``throughput``, ``repro.sim.cells``), kept here so that the
traffic a cell is measured on cannot move with the program. It takes the
program's ``EpisodeBatch`` and ``ChurnSchedule`` types as its interface and
nothing else. One departure: IQ is float32, as a RAN reports it (the
program's copy multiplies float32 noise by a float64 std).
"""
from __future__ import annotations

import math

import numpy as np

SCENARIOS = ("none", "jamming", "cci", "tdd")
WINDOW = 30  # KPM reports the windowed estimator reads (paper: 30)
N_PRB = 273
N_SC = N_PRB * 12  # 3276 subcarriers: 100 MHz at 30 kHz SCS
N_SYM = 14

# throughput model: zone boundaries in interference dBm at the gNB
NEGLIGIBLE_MAX, POWER_CTRL_MAX, MCS_CTRL_MAX = -20.0, -5.0, 8.0
PEAK_MBPS = 130.0
SIG_DBM_BASE, TPC_MAX_DB, NOISE_FLOOR_DBM = -10.0, 15.0, -35.0

KPMS_15 = ["rsrp", "rsrq", "sinr", "p_a", "ri", "cqi", "cri", "pusch_sinr",
           "tpc", "ul_mcs", "ul_bler", "harq_rv0", "harq_rv1", "harq_rv2",
           "harq_rv3"]
SCENARIO_OVERLAP = {"none": 0.0, "jamming": 0.8, "cci": 0.35, "tdd": 0.6}


def tpc_boost_db(x):
    return TPC_MAX_DB * np.clip((np.asarray(x, float) - NEGLIGIBLE_MAX)
                                / (POWER_CTRL_MAX - NEGLIGIBLE_MAX), 0.0, 1.0)


def sinr_db(x):
    x = np.asarray(x, float)
    noise_mw = 10 ** (NOISE_FLOOR_DBM / 10) + 10 ** (x / 10)
    return SIG_DBM_BASE + tpc_boost_db(x) - 10 * np.log10(noise_mw)


def max_throughput_mbps(x):
    """Max achievable uplink rate (Mbps) at a full grant."""
    cap = np.log2(1 + 10 ** (sinr_db(x) / 10))
    peak_cap = np.log2(1 + 10 ** (sinr_db(np.array(-60.0)) / 10))
    tp = PEAK_MBPS * np.minimum(cap / peak_cap, 1.0)
    ooc = np.clip((np.asarray(x, float) - MCS_CTRL_MAX) / 4.0, 0, 1)
    return np.maximum(tp * (1 - 0.97 * ooc), 0.5)


def bler(x):
    x = np.clip((np.asarray(x, float) - MCS_CTRL_MAX) / 3.0, 0, 1)
    return 0.1 + 0.9 * x ** 2


def mcs_index(x):
    x = np.clip((np.asarray(x, float) - POWER_CTRL_MAX)
                / (MCS_CTRL_MAX - POWER_CTRL_MAX), 0, 1)
    return np.round(28 * (1 - x)).astype(int)


def power_sum_dbm(base_dbm, extra_mw):
    p_mw = 10 ** (np.asarray(base_dbm, float) / 10) + np.asarray(extra_mw,
                                                                 float)
    return np.minimum(10 * np.log10(np.maximum(p_mw, 1e-12)), 14.0)


def interference_traces(scen, T, rng, extra_mw=None):
    """(N, T) interference power (dBm), one trace per scenario name."""
    n = len(scen)
    base = rng.uniform(-30, 10, n)
    walk = np.cumsum(rng.normal(0, 1.0, (n, T)), axis=1)
    tr = base[:, None] + walk - walk.mean(axis=1, keepdims=True)
    on = np.sin(np.arange(T)[None] / rng.uniform(3, 10, n)[:, None]) > -0.3
    tr = np.where((scen == "jamming")[:, None] & ~on, -60.0, tr)
    tr = np.where((scen == "none")[:, None], -60.0, np.clip(tr, -60, 14))
    return tr if extra_mw is None else power_sum_dbm(tr, extra_mw)


def kpm_reports(x, lr, rng, scen):
    """(N, T, 15) float32 KPM reports for (N, T) interference traces."""
    n, T = x.shape
    ov = np.array([SCENARIO_OVERLAP[str(s)] for s in scen])[:, None]
    ov = np.broadcast_to(ov, (n, T))

    def noise(s):
        return rng.normal(0.0, s, (n, T))

    out = np.empty((n, T, len(KPMS_15)), np.float32)
    col = {k: i for i, k in enumerate(KPMS_15)}
    out[:, :, col["rsrp"]] = -85.0 + noise(1.0)
    out[:, :, col["rsrq"]] = -10.5 + noise(0.5)
    out[:, :, col["sinr"]] = 22.0 + noise(1.0)
    out[:, :, col["p_a"]] = -3.0 + noise(0.2)
    out[:, :, col["ri"]] = 2.0 + (rng.random((n, T)) < 0.05)
    out[:, :, col["cqi"]] = 13.0 + np.round(noise(0.6))
    out[:, :, col["cri"]] = 1.0
    visible = np.maximum(np.clip((lr - 0.15) / 0.85, 0.0, 1.0)[:, None], ov)
    eff = x * visible + (-60.0) * (1 - visible)
    b = bler(eff)
    out[:, :, col["pusch_sinr"]] = sinr_db(eff) + noise(0.8)
    out[:, :, col["tpc"]] = tpc_boost_db(eff) + noise(0.3)
    out[:, :, col["ul_mcs"]] = mcs_index(eff)
    out[:, :, col["ul_bler"]] = np.clip(b + noise(0.02), 0, 1)
    bp = np.minimum(b, 1.0)
    tbs = rng.poisson(80 * lr[:, None] + 1, (n, T))
    rv1 = rng.binomial(tbs, bp)
    rv2 = rng.binomial(rv1, bp)
    rv3 = rng.binomial(rv2, bp)
    for k, inc in (("harq_rv0", tbs), ("harq_rv1", rv1), ("harq_rv2", rv2),
                   ("harq_rv3", rv3)):
        out[:, :, col[k]] = np.cumsum(inc, axis=1)
    return out


def footprint(scenario, m, rng, n_sc):
    """(m, n_sc, N_SYM) float32 in {0, 1}: where interference lands."""
    sc = (np.arange(n_sc) if n_sc == N_SC
          else np.linspace(0, N_SC - 1, n_sc).astype(int))
    if scenario == "none":
        return np.zeros((m, n_sc, N_SYM), np.float32)
    if scenario == "jamming":
        f0 = rng.integers(0, N_SC // 4, m)
        f1 = rng.integers(3 * N_SC // 4, N_SC, m)
        band = (sc[None] >= f0[:, None]) & (sc[None] < f1[:, None])
        sym = rng.random((m, N_SYM)) < 0.8
        return (band[:, :, None] & sym[:, None, :]).astype(np.float32)
    if scenario == "cci":
        prb = sc // 12
        n_blocks = rng.integers(2, 6, m)
        p0 = rng.integers(N_PRB // 8, N_PRB, (m, 5))
        w = rng.integers(8, 40, (m, 5))
        live = np.arange(5)[None] < n_blocks[:, None]
        hit = (live[:, :, None] & (prb[None, None] >= p0[:, :, None])
               & (prb[None, None] < (p0 + w)[:, :, None])).any(axis=1)
        return np.repeat(hit[:, :, None].astype(np.float32), N_SYM, axis=2)
    if scenario == "tdd":
        one = ((sc[:, None] >= N_SC // 10)
               & (np.arange(N_SYM)[None] >= 8)).astype(np.float32)
        return np.repeat(one[None], m, axis=0)
    raise ValueError(scenario)


def spectrograms(x, scen, lr, rng, n_sc):
    """(m, 2, n_sc, N_SYM) float32 uplink IQ grids for m slots."""
    m = len(x)
    fp = np.empty((m, n_sc, N_SYM), np.float32)
    for s in np.unique(scen):
        idx = np.flatnonzero(scen == s)
        fp[idx] = footprint(str(s), len(idx), rng, n_sc)
    n_alloc = np.maximum(1, (lr * n_sc).astype(int))
    alloc = (np.arange(n_sc)[None] < n_alloc[:, None]).astype(np.float32)
    var = (np.float32(10 ** (-10.0 / 10)) * alloc[:, :, None]
           + (10 ** (x / 10)).astype(np.float32)[:, None, None] * fp
           + np.float32(10 ** (-35.0 / 10)))
    std = np.sqrt(var * np.float32(0.5))
    iq = rng.standard_normal((m, 2, n_sc, N_SYM), dtype=np.float32)
    iq *= std[:, None]
    return iq


def episode(EpisodeBatch, scen, T, rng, *, n_sc=N_SC, include_iq=True,
            extra_mw=None, load_ratio=None):
    """An ``EpisodeBatch`` of N UEs over T report periods (plus the WINDOW
    warm-up reports)."""
    scen = np.asarray(scen)
    n = len(scen)
    lr = rng.uniform(0.05, 1.0, n) if load_ratio is None else load_ratio
    tr = interference_traces(scen, T + WINDOW, rng, extra_mw)
    kpms = kpm_reports(tr, lr, rng, scen)
    tp = max_throughput_mbps(tr[:, WINDOW:])
    iq = None
    if include_iq:
        iq = spectrograms(tr[:, WINDOW:].ravel(), np.repeat(scen, T),
                          np.repeat(lr, T), rng, n_sc
                          ).reshape(n, T, 2, n_sc, N_SYM)
    sidx = np.array([SCENARIOS.index(s) for s in scen])
    return EpisodeBatch(scenario_idx=sidx, alloc_ratio=lr, int_dbm=tr,
                        kpms=kpms, tp_mbps=tp, iq=iq)


def ring_coupling(n_cells, neighbor_dbm=-12.0, decay=0.5):
    """(C, C) mW a fully loaded cell j injects at cell i's gNB."""
    d = np.abs(np.arange(n_cells)[:, None] - np.arange(n_cells)[None])
    d = np.minimum(d, n_cells - d)
    return np.where(d == 0, 0.0, 10 ** (neighbor_dbm / 10) * decay ** (d - 1.0))


def coupled_interference_mw(cell, lr, T, coupling):
    """(N, T) neighbour-cell interference floor (mW) for a static attach:
    each cell's mean offered load through the coupling matrix."""
    n_cells = coupling.shape[0]
    cnt = np.bincount(cell, minlength=n_cells)
    load = np.bincount(cell, weights=lr, minlength=n_cells) / np.maximum(cnt,
                                                                      1)
    return np.repeat((coupling @ load)[cell][:, None], T, axis=1)


def churn(ChurnSchedule, arrival_rate, mean_dwell, diurnal_amplitude, T,
          rng):
    """Poisson arrivals with a diurnal tide over the horizon and geometric
    dwell capped at 3x the mean, as a ``ChurnSchedule``."""
    lam = arrival_rate * (1.0 + diurnal_amplitude
                          * np.sin(2.0 * np.pi * np.arange(T) / T))
    counts = rng.poisson(np.maximum(lam, 0.0)).astype(np.int64)
    arrival_t = np.repeat(np.arange(T, dtype=np.int32), counts)
    cap = max(int(math.ceil(3.0 * mean_dwell)), 1)
    dwell = np.clip(rng.geometric(1.0 / mean_dwell, len(arrival_t)), 1,
                    cap).astype(np.int32)
    return ChurnSchedule(arrival_t=arrival_t, dwell=dwell,
                         ready_end=np.cumsum(counts).astype(np.int32),
                         horizon=int(T),
                         max_admits=max(1, 2 * int(counts.max(initial=0))))
