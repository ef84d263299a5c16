"""How ``correct`` is decided: what the timed ``simulate_fleet`` calls
produced, against the plain reference, on the calls sampled from the
window.

The numbers, each the worst over the sampled calls; a cell compares
those its limits file (``bench/limits/<cell>.json``) names:

  est_gap_mbps  the widest gap between the estimate each controller
                consumed (clipped into 1..130 Mbps, times the PRB share
                where a scheduler runs) and the reference's: the estimator
                forward before the clip, the PF shares and the clip.

The reference's estimates come at each precision the configuration names
(``reference_precisions``: the one it states, and float32), and each
estimate number is taken against the nearer: a program at the stated
precision or above it passes, one below it does not.
  splits_off    UE-periods whose split, or whose slot occupancy, differs
                from the reference's controllers (and slot pool) replayed
                on the estimates the program's controllers consumed.
                Exact: the controllers' arithmetic is exact in float32.
  metrics_off   UE-periods whose ground-truth rate, delay, privacy or
                energy differs from the reference's split metrics at the
                program's own splits and PRB shares. Exact (float64).

A split target whose tail the edge serves adds its own numbers (its
``names``), the tail's gaps to the target's own plain reference at the
program's splits, from its ``check`` hook (``harness/splitting.py``).

The gap's median and 99th percentile, the share of estimates more than
one table bucket (1 Mbps) off, and each against every precision apart, are
reported beside them (by ``bench/control.py``), not compared.
"""
from __future__ import annotations

import numpy as np

from harness import reference as ref

TP_CLIP = (1.0, 130.0)
# the numbers a cell's limits file may name (bench/limits/<cell>.json),
# with its split target's ``names``
NAMES = ("est_gap_mbps", "splits_off", "metrics_off")
FAR = 1e300  # a reading that fails every limit and still prints as JSON
BUCKET_MBPS = 1.0  # the lookup table's bucket: the controller rounds to it


def names(target) -> tuple:
    """The numbers a cell whose split target is ``target`` may limit."""
    return NAMES + tuple(getattr(target, "names", ()))


def reference_outputs(hz, raw, tgt, config, sched):
    """The reference's consumed estimates, splits and occupancy for one
    horizon, in the program's (rows, periods) layout. ``raw``: the
    reference estimator's unclipped output, (N, T) or per session (M, L)."""
    est = np.clip(np.asarray(raw, np.float64), *TP_CLIP)
    ctl = config["controller"]
    if hz.kind == "fleet":
        return {"est": est, "splits": ref.fleet_splits(
            tgt["table"], est.astype(np.float32), ctl), "active": None}
    true = np.asarray(hz.episode.tp_mbps, np.float64)
    tr = ref.pool(hz.schedule, hz.capacity, est, true, hz.cell, ctl,
                  tgt["table"], sched, hz.n_cells)
    sid = np.clip(tr.sid, 0, est.shape[0] - 1)
    consumed = np.where(tr.active, est[sid, tr.age], 0.0)
    if sched is not None:
        consumed = consumed * np.where(tr.active, tr.share, 0.0)
    return {"est": consumed, "splits": tr.splits, "active": tr.active}


def gaps(result, expects: dict) -> dict:
    """The estimate numbers of one program result against the reference at
    each precision the configuration names (``reference_precisions``); a
    number is taken against the nearer reference, so a program at the
    stated precision or above it passes, and one below it does not."""
    est_p = np.asarray(result.est_tp, np.float64)
    act = (np.ones(est_p.shape, bool) if result.active is None
           else np.asarray(result.active, bool))
    out = {}
    for prec, expect in expects.items():
        gap = np.abs(est_p - expect["est"])[act]
        bad = gap.size and not np.isfinite(gap).all()
        row = {"est_gap_mbps": float(gap.max()) if gap.size else 0.0,
               "est_far_share": float(np.mean(~(gap <= BUCKET_MBPS)))
               if gap.size else 0.0}
        row.update({f"est_gap_p{q}_mbps": float(np.percentile(gap, q))
                    if gap.size else 0.0 for q in (50, 99)})
        if bad:
            row = {k: FAR for k in row}
        for k, v in row.items():
            out[k] = min(out.get(k, FAR), v)
            out[f"{k}.{prec}"] = v
    return out


def numbers(hz, result, expects, tgt, config, sched, target=None,
            target_params=None) -> dict:
    """Every number of one program result (see the module's docstring);
    ``target``: the split target's module, whose ``check`` hook adds the
    served tail's numbers."""
    ctl = config["controller"]
    est_p = np.asarray(result.est_tp, np.float64)
    act = (np.ones(est_p.shape, bool) if result.active is None
           else np.asarray(result.active, bool))
    consumed = est_p.astype(np.float32)  # exact: float32 values times shares
    true = np.asarray(hz.episode.tp_mbps, np.float64)
    if hz.kind == "fleet":
        splits = ref.fleet_splits(tgt["table"], consumed, ctl)
        occ_off = 0
        true_r = true
        eff = true_r
    else:
        tr = ref.pool(hz.schedule, hz.capacity, consumed, true, hz.cell, ctl,
                      tgt["table"], sched, hz.n_cells, consumed_by_slot=True)
        splits = tr.splits
        occ_off = int((tr.active != act).sum())
        sid = np.clip(tr.sid, 0, true.shape[0] - 1)
        true_r = np.where(tr.active, true[sid, tr.age], 0.0)
        eff = (true_r if sched is None
               else ref.prb_scaled(true_r, result.prb_share))
    splits_off = occ_off + int((np.asarray(result.splits) != splits)[act].sum())
    l = np.where(act, np.asarray(result.splits), 0)
    d, p, e = ref.split_metrics(tgt, l, eff)
    differs = ((np.asarray(result.true_tp) != true_r)
               | (np.asarray(result.delay_s) != d)
               | (np.asarray(result.privacy) != p)
               | (np.asarray(result.energy_j) != e))
    out = {**gaps(result, expects), "splits_off": splits_off,
           "metrics_off": int(differs[act].sum())}
    tail = getattr(target, "check", None)
    if tail is not None:
        out.update(tail(config["split_target"], target_params, hz,
                        hz.payload, result))
    return out


def worst(rows) -> dict:
    """The worst of each number over several results."""
    keys = dict.fromkeys(k for r in rows for k in r)
    return {k: max(r[k] for r in rows if k in r) for k in keys}


def verdict(nums: dict, limits: dict) -> bool:
    """Every number the cell's limits name is there and within its limit."""
    return all(k in nums and nums[k] <= v for k, v in limits.items())
