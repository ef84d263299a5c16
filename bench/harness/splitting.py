"""What every split target shares: the per-split delays and UE energy
from its profile and the configuration's devices, and the PSO lookup table
of Algorithm 1 (one objective evaluation over every split and integer Mbps).

A configuration's ``split_target.model`` names its module,
``bench/targets/<model>.py``, which supplies

  profile(spec) -> (flops_head, data_bytes, privacy)
                   cumulative head FLOPs, boundary bytes and dCor per split
  name(spec)    -> the name the program's ``SplitProfile`` carries

where ``spec`` is the ``split_target`` block. A target whose tail the edge
serves inside the fleet path may add hooks; the harness calls each only
where the module has it, and none inside the measured window except
``call_kwargs``:

  init_params(spec, key)          its seeded weights, made on the device in
                                  set-up after the estimator's
  payload(spec, params, target_mix, hz, rng)
                                  per horizon, what the UEs send, from the
                                  mix's optional ``target`` block; kept
                                  with the horizon as ``hz.payload``
  call_kwargs(spec, params, payload) -> dict
                                  merged into ``simulate_fleet``'s keyword
                                  arguments on every call
  names                           a tuple: the numbers ``check`` returns,
                                  which a cell's limits file may name
  check(spec, params, hz, payload, result) -> {name: number}
                                  the tail's gaps to the target's own plain
                                  reference, at the program's own splits

A copy of the program's ``core.objective``, ``core.energy`` and
``core.pso``, so that the table the program is handed, and the reference's
split metrics, are made here and not by the code under test.
"""
from __future__ import annotations

import numpy as np

NO_SPLIT = -1
TP_FLOOR_BPS = 1.0  # a starved link's delay stays finite
PRB_FLOOR_MBPS = 0.01


def split_target(spec, target):
    """Per-split arrays, the profile's name and the lookup table for a
    config's ``split_target`` block, profiled by its module ``target``."""
    flops_head, data, privacy = (np.asarray(a, float)
                                 for a in target.profile(spec))
    ue, edge = spec["ue"], spec["edge"]
    d_ue = flops_head / ue["flops_per_s"]
    d_ser = edge["fixed_latency_s"] + (flops_head[-1] - flops_head) / edge[
        "flops_per_s"]
    e_ue = ue["tdp_w"] / ue["threads"] * d_ue
    tgt = dict(name=target.name(spec), flops_head=flops_head,
               data_bytes=data, privacy=privacy, d_ue=d_ue, d_ser=d_ser,
               e_ue=e_ue)
    tgt["table"] = pso_table(tgt, spec)
    return tgt


def pso_table(t, spec):
    """Algorithm 1: for every integer Mbps, the feasible split with the
    least normalised weighted delay + privacy + energy."""
    w_d, w_p, w_e = spec["weights"]
    tau, rho, e_max = spec["tau_max_s"], spec["rho_max"], spec["e_max_j"]
    tps = np.arange(1, spec["tp_max_mbps"] + 1) * 1e6
    d_e2e = (t["d_ue"] + t["d_ser"])[:, None] + t["data_bytes"][:, None] \
        * 8.0 / tps[None]
    p, e = t["privacy"], t["e_ue"]
    f = (w_d * d_e2e / max(float(np.max(t["d_ue"] + t["d_ser"])), 1e-9)
         + w_p * (p / max(float(p.max()), 1e-9))[:, None]
         + w_e * (e / max(float(e.max()), 1e-9))[:, None])
    pre = (p <= rho) & (e <= e_max)
    slack = tau - t["d_ue"] - t["d_ser"]
    with np.errstate(divide="ignore"):
        tp_min = np.where(slack > 0, t["data_bytes"] * 8.0
                          / np.maximum(slack, 1e-12), np.inf)
    usable = (pre[:, None] & (tp_min[:, None] <= tps[None])
              & (d_e2e <= tau))
    f = np.where(usable, f, np.inf)
    best = np.argmin(f, axis=0)
    ok = np.isfinite(f[best, np.arange(len(tps))])
    table = np.full(len(tps) + 1, NO_SPLIT, np.int32)
    table[1:] = np.where(ok, best, NO_SPLIT)
    return table
