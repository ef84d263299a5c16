"""Plain reference of everything after the estimator: the per-UE split
controller (EWMA, table lookup, hysteresis), the proportional-fair PRB
scheduler, the churned slot pool (FIFO admission into a free-slot stack,
retirement at the session's dwell) and the split metrics.

NumPy loops over report periods, vectorised over UEs or slots; the state
that the program keeps in float32 is kept in float32 here. It imports
nothing of the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness.splitting import NO_SPLIT, PRB_FLOOR_MBPS, TP_FLOOR_BPS

PENDING_NONE = -2
F32 = np.float32


@dataclasses.dataclass
class Controllers:
    """The state of one split controller per UE or slot."""

    ewma: np.ndarray
    has: np.ndarray
    cur: np.ndarray
    pend: np.ndarray
    cnt: np.ndarray

    @classmethod
    def fresh(cls, n, warm):
        return cls(np.zeros(n, F32), np.zeros(n, bool), np.full(n, warm),
                   np.full(n, PENDING_NONE), np.zeros(n, np.int64))

    def reset(self, idx, warm):
        self.ewma[idx], self.has[idx] = 0.0, False
        self.cur[idx], self.pend[idx], self.cnt[idx] = warm, PENDING_NONE, 0

    def step(self, table, tp, ctl):
        """Consume one report per controller; returns the deployed split."""
        a = F32(ctl["ewma_alpha"])
        tp = np.asarray(tp, F32)
        self.ewma = np.where(self.has, a * tp + (F32(1) - a) * self.ewma,
                             tp).astype(F32)
        self.has[:] = True
        bucket = np.clip(np.round(self.ewma).astype(np.int64), 0,
                         len(table) - 1)
        prop = table[bucket].astype(np.int64)
        prop = np.where(prop == NO_SPLIT, ctl["fallback_split"], prop)
        differs = prop != self.cur
        cnt = np.where(prop == self.pend, self.cnt + 1, 1)
        switch = differs & (cnt >= ctl["hysteresis_steps"])
        keep = differs & ~switch
        self.cur = np.where(switch, prop, self.cur)
        self.pend = np.where(keep, prop, PENDING_NONE)
        self.cnt = np.where(keep, cnt, 0)
        return self.cur.copy()


def fleet_splits(table, est, ctl):
    """(N, T) splits of N controllers over T periods of consumed
    estimates."""
    c = Controllers.fresh(est.shape[0], ctl["fallback_split"])
    return np.stack([c.step(table, est[:, t], ctl)
                     for t in range(est.shape[1])], 1)


def pf_shares(avg, rate, cell, active, n_cells, eps, beta):
    """One period of masked proportional-fair scheduling: (shares, new PF
    averages). Empty slots get no PRBs and keep their average."""
    w = np.where(active, rate / np.maximum(avg, F32(eps)), F32(0)).astype(F32)
    seg = np.where(active, cell, n_cells)
    denom = np.bincount(seg, weights=w, minlength=n_cells + 1).astype(F32)
    share = (w / np.maximum(denom[seg], F32(eps))).astype(F32)
    b = F32(beta)
    new = np.where(active, (F32(1) - b) * avg + b * rate * share, avg)
    return share, new.astype(F32)


@dataclasses.dataclass
class PoolTrace:
    """(S, T) per-slot view of one horizon."""

    active: np.ndarray
    sid: np.ndarray
    age: np.ndarray
    splits: np.ndarray
    share: np.ndarray | None


def pool(schedule, capacity, consumed, true_tp, cell, ctl, table, sched,
         n_cells, *, consumed_by_slot=False):
    """Run the slot pool over the horizon.

    ``consumed``: (M, L) per-session estimates by age, or with
    ``consumed_by_slot`` the (S, T) per-slot estimates the controllers
    consumed (then no scheduler share is applied again). ``true_tp`` (M, L)
    drives the scheduler. ``sched``: None or {"policy": "pf", "eps",
    "pf_beta"}."""
    s_n = int(capacity)
    t_n = schedule.horizon
    m = len(schedule.arrival_t)
    lanes = schedule.max_admits
    active = np.zeros(s_n, bool)
    sid = np.zeros(s_n, np.int64)
    age = np.zeros(s_n, np.int64)
    free = np.arange(s_n - 1, -1, -1)
    n_free, nxt = s_n, 0
    warm = ctl["fallback_split"]
    ctls = Controllers.fresh(s_n, warm)
    avg = np.ones(s_n, F32)
    out = {k: np.zeros((s_n, t_n), d) for k, d in
           (("active", bool), ("sid", np.int64), ("age", np.int64),
            ("splits", np.int64), ("share", F32))}
    for t in range(t_n):
        k = min(int(schedule.ready_end[t]) - nxt, n_free, lanes)
        slots = free[n_free - 1 - np.arange(k)]
        active[slots], sid[slots], age[slots] = True, nxt + np.arange(k), 0
        ctls.reset(slots, warm)
        avg[slots] = 1.0
        n_free -= k
        nxt += k
        sidc = np.clip(sid, 0, m - 1)
        if consumed_by_slot:
            est_t = np.asarray(consumed[:, t], F32)
        else:
            est_t = np.where(active, consumed[sidc, age], 0).astype(F32)
        true_t = np.where(active, true_tp[sidc, age], 0).astype(F32)
        if sched is None:
            share = active.astype(F32)
            eff = est_t
        else:
            share, avg = pf_shares(avg, true_t, cell[sidc], active, n_cells,
                                   sched["eps"], sched["pf_beta"])
            eff = est_t if consumed_by_slot else (est_t * share).astype(F32)
        split = np.where(active, ctls.step(table, eff, ctl), NO_SPLIT)
        for key, v in (("active", active), ("sid", sid), ("age", age),
                       ("splits", split), ("share", share)):
            out[key][:, t] = v
        dep = active & (age + 1 >= schedule.dwell[sidc])
        n_dep = int(dep.sum())
        free[n_free:n_free + n_dep] = np.flatnonzero(dep)
        n_free += n_dep
        active &= ~dep
        age = np.where(active, age + 1, age)
    return PoolTrace(out["active"], out["sid"], out["age"], out["splits"],
                     out["share"] if sched is not None else None)


def split_metrics(tgt, splits, eff_tp):
    """(delay_s, privacy, energy_j) at the deployed splits, float64."""
    tp_bps = np.maximum(np.asarray(eff_tp, float) * 1e6, TP_FLOOR_BPS)
    d = tgt["d_ue"][splits] + tgt["d_ser"][splits] + tgt["data_bytes"][
        splits] * 8.0 / tp_bps
    return d, tgt["privacy"][splits], tgt["e_ue"][splits]


def prb_scaled(tp, share):
    return np.maximum(np.asarray(tp, float) * np.clip(np.asarray(share, float),
                                                      0.0, 1.0),
                      PRB_FLOOR_MBPS)
