"""The one traffic generator: turns a mix's parameter file
(``bench/traffic/<name>.json``) and a seed into the horizons a cell cycles
through. A horizon is what one ``simulate_fleet`` call decides: N UEs (or a
churned session set in a slot pool) over H report periods of 100 ms.

Kinds of mix:
  fleet: ``ues`` UEs for ``periods`` periods, scenarios S0-S3 round-robin,
         each UE's PRB load ratio drawn from ``load_ratio``.
  pool:  ``cells`` x ``slots_per_cell`` slots over ``periods`` periods;
         Poisson arrivals at ``arrivals_per_slot`` x slots per period with
         a diurnal tide of ``diurnal_amplitude`` over the horizon, geometric
         dwell of mean ``mean_dwell`` periods; sessions attach to cells
         round-robin on a ring, optionally coupled (``coupling: "ring"``),
         under ``scheduler`` (null, or the PF scheduler's settings).

Keys any mix may add:
  serving: a ``DxM`` mesh string (``"4x1"``): ``simulate_fleet`` serves the
           estimator on ``repro.sim.make_serving_mesh(serving)``.
  target:  the split target's parameters, read by its ``payload`` hook
           (``harness/splitting.py``) in set-up, never here.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from harness import channel


@dataclasses.dataclass
class Horizon:
    """One horizon: the episode and what ``simulate_fleet`` is given."""

    kind: str
    episode: object  # EpisodeBatch
    schedule: object = None  # ChurnSchedule (pool)
    cell: np.ndarray | None = None  # (M,) static attach (pool)
    capacity: int = 0
    n_cells: int = 1
    scheduler: dict | None = None  # {"policy": "pf", "pf_beta", "eps", ...}
    payload: object = None  # what the UEs send the split target's tail

    @property
    def shape(self):
        """What the cost functions read: UEs x periods of a fleet, or
        sessions x trace reports of a pool."""
        ep = self.episode
        if self.kind == "fleet":
            return {"ues": ep.tp_mbps.shape[0], "periods": ep.tp_mbps.shape[1]}
        return {"sessions": ep.tp_mbps.shape[0],
                "steps": ep.tp_mbps.shape[1] + channel.WINDOW}


def seed_rng(seed: int, salt: int = 0) -> np.random.Generator:
    """NumPy generator for any whole-number seed (negative ones too)."""
    return np.random.default_rng([int(seed) % (1 << 63), salt])


def make(mix: dict, seed: int, *, n_sc: int, include_iq: bool,
         EpisodeBatch, ChurnSchedule) -> list[Horizon]:
    """The mix's ``horizons`` distinct horizons for ``seed``."""
    rng = seed_rng(seed)
    out = []
    for _ in range(int(mix["horizons"])):
        if mix["kind"] == "fleet":
            n = int(mix["ues"])
            scen = np.asarray(channel.SCENARIOS)[np.arange(n)
                                                 % len(channel.SCENARIOS)]
            lr = rng.uniform(*mix["load_ratio"], n)
            ep = channel.episode(EpisodeBatch, scen, int(mix["periods"]), rng,
                                 n_sc=n_sc, include_iq=include_iq,
                                 load_ratio=lr)
            out.append(Horizon("fleet", ep))
        elif mix["kind"] == "pool":
            out.append(_pool(mix, rng, n_sc, include_iq, EpisodeBatch,
                             ChurnSchedule))
        else:
            raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    return out


def _pool(mix, rng, n_sc, include_iq, EpisodeBatch, ChurnSchedule):
    cells = int(mix["cells"])
    cap = cells * int(mix["slots_per_cell"])
    sch = channel.churn(ChurnSchedule, mix["arrivals_per_slot"] * cap,
                        mix["mean_dwell"], mix["diurnal_amplitude"],
                        int(mix["periods"]), rng)
    m = sch.n_sessions
    cell = np.arange(m) % cells
    scen = np.asarray(channel.SCENARIOS)[np.arange(m) % len(channel.SCENARIOS)]
    lr = rng.uniform(*mix["load_ratio"], m)
    trace = sch.max_dwell
    extra = None
    if mix.get("coupling") == "ring":
        extra = channel.coupled_interference_mw(
            cell, lr, trace + channel.WINDOW, channel.ring_coupling(cells))
    ep = channel.episode(EpisodeBatch, scen, trace, rng, n_sc=n_sc,
                         include_iq=include_iq, extra_mw=extra, load_ratio=lr)
    return Horizon("pool", ep, sch, cell, cap, cells, mix.get("scheduler"))


def checksum(horizons) -> str:
    """A digest of every array of the horizons, for the generator's pinned
    seed-0 output."""
    import hashlib
    h = hashlib.sha256()
    for hz in horizons:
        ep = hz.episode
        for a in (ep.alloc_ratio, ep.int_dbm, ep.kpms, ep.tp_mbps, ep.iq):
            if a is not None:  # 4 decimals: blind to last-bit libm changes
                h.update(np.round(np.asarray(a, np.float64), 4).tobytes())
        if hz.schedule is not None:
            for a in (hz.schedule.arrival_t, hz.schedule.dwell,
                      hz.schedule.ready_end, hz.cell):
                h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()
