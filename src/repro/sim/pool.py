"""Slot-pool fleet engine: continuous UE arrival/departure at fixed shape.

The batch-synchronous engine (``repro.sim.engine``) marches one fixed
N-UE cohort through T report periods in lockstep. Real traffic churns:
UEs attach, live for a while, and detach continuously — and a jitted
program whose shapes track the live population would retrace on every
arrival. This module keeps the *shapes* fixed and lets the *population*
move: a device-resident pool of ``capacity`` UE slots, an active mask,
and a free-list index stack (the replay-ring scatter idiom from
``repro.sim.online``) are threaded through one unified per-period step:

  admit    — pop free slots for the FIFO's ready arrivals through
             ``max_admits`` fixed lanes (excess arrivals queue and show
             up as admission latency); scatter-reset the slot's
             controller + scheduler state (``mode="drop"`` discards the
             unused lanes, so the write is one fixed-shape scatter);
  serve    — gather each active slot's session trace at its age, run the
             gNB scheduler masked to active slots
             (``scheduler_step(active=...)``: empty slots get no PRBs and
             shape no cell normalizer) and the split controllers as one
             ``vmap`` over slots;
  retire   — push slots whose sessions reached their dwell back onto the
             free stack (cumsum-packed scatter) and clear their mask.

The whole horizon runs as one ``lax.scan`` over periods (or a host loop
with the same jitted sub-steps when online adaptation must interleave),
so the compiled program is a function of (capacity, horizon, session
count, lanes) only — occupancy can swing 10–90% without a retrace.

Sessions come from ``repro.channel.scenarios.make_churn_schedule`` (the
arrival/dwell realisation) plus an ``EpisodeBatch`` with one row per
session (its channel life). ``simulate_fleet(churn=...)`` is the public
entry; ``churn=None`` never enters this module (the engine's
batch-synchronous path runs; a degenerate full pool is pinned equal to
it by ``tests/test_sim_pool.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.channel import throughput as tpmod
from repro.channel.scenarios import WINDOW, ChurnSchedule, EpisodeBatch
from repro.core.controller import (PENDING_NONE, ControllerConfig,
                                   ControllerState, controller_init,
                                   controller_step)
from repro.core.energy import EDGE_A40X2, UE_VM_2CORE, DeviceProfile
from repro.core.profiles import SplitProfile
from repro.core.pso import NO_SPLIT, TP_CLIP_MBPS, StackedLookupTable
from repro.sim import telemetry as telmod
from repro.sim.sched import (SchedulerConfig, SchedulerState, scheduler_init,
                             scheduler_step)
from repro.sim.serving import ServingMesh
from repro.sim.telemetry import TelemetryConfig

F32 = jnp.float32
I32 = jnp.int32


class PoolState(NamedTuple):
    """The device-resident slot pool carried across report periods.

    ``free[:n_free]`` is a stack of currently-empty slot indices; every
    slot is either active or on the stack, never both (the conservation
    invariant ``tests/test_sim_pool.py`` pins). ``next_arrival`` is the
    pool's cursor into the global admission FIFO."""

    active: jax.Array  # (S,) bool — slot holds a live session
    sid: jax.Array  # (S,) i32 — session id occupying the slot
    age: jax.Array  # (S,) i32 — periods served so far (0 on admission)
    free: jax.Array  # (S,) i32 — stack of free slot indices
    n_free: jax.Array  # i32 scalar — stack depth
    next_arrival: jax.Array  # i32 scalar — FIFO cursor
    ctl: ControllerState  # (S,)-batched controller states
    sched: SchedulerState  # (S,)-batched scheduler state


def pool_init(capacity: int, warm_split=NO_SPLIT,
              avg0: float = 1.0) -> PoolState:
    """An empty pool: every slot on the free stack, ordered so slot 0 is
    admitted first (readable traces; any order is correct)."""
    if capacity <= 0:
        raise ValueError(f"capacity must be positive: {capacity}")
    s = int(capacity)
    return PoolState(
        active=jnp.zeros((s,), bool),
        sid=jnp.zeros((s,), I32),
        age=jnp.zeros((s,), I32),
        free=jnp.arange(s - 1, -1, -1, dtype=I32),
        n_free=jnp.asarray(s, I32),
        next_arrival=jnp.zeros((), I32),
        ctl=controller_init(warm_split, batch_shape=(s,)),
        sched=scheduler_init(s, avg0))


class PoolPrograms(NamedTuple):
    """Jitted per-period programs for one (controller, scheduler, lanes)
    config. ``sweep`` runs the whole horizon as one scan; ``admit`` and
    ``serve_retire`` are the same step split in two so a host loop (the
    online path, or an invariant test) can interleave work between
    admission and service; ``gather`` pulls the active slots' estimator
    inputs for a live forward."""

    sweep: object
    admit: object
    serve_retire: object
    gather: object


@functools.lru_cache(maxsize=None)
def pool_programs(ewma_alpha: float, hysteresis_steps: int,
                  fallback_split: int,
                  sched: Optional[SchedulerConfig] = None, n_cells: int = 1,
                  max_admits: int = 1,
                  telem: Optional[TelemetryConfig] = None) -> PoolPrograms:
    """Compile the pool step once per configuration (jit's own cache then
    handles distinct (capacity, sessions, horizon) shapes — churn moves
    the population, never the shapes, so the program never retraces).

    ``sweep``'s scan body is ``admit``, the gather of each slot's
    estimate, then the same serve-and-retire core ``serve_retire`` wraps.
    With ``telem`` its carry also holds a ``TelemetryState`` (passed in as
    ``ts0``), folding each period's masked metrics into it and logging
    per-lane EV_ADMIT / aggregate EV_DEPART events into the device ring;
    it returns ``((state, ts), (act, sid, age, split, share, lat, n_dep,
    row))``. That is a Python branch at trace time: with ``telem=None``
    ``ts`` and ``row`` are None and the program holds nothing of the
    metric plane."""
    cfg = ControllerConfig(ewma_alpha, hysteresis_steps, fallback_split)
    step = functools.partial(controller_step, cfg=cfg)
    a_lanes = int(max_admits)

    def _admit(st: PoolState, t, ready_end_t, arrival_t, warm):
        s = st.active.shape[0]
        m = arrival_t.shape[0]
        lane = jnp.arange(a_lanes, dtype=I32)
        k = jnp.minimum(jnp.minimum(ready_end_t - st.next_arrival,
                                    st.n_free), a_lanes)
        valid = lane < k
        sid_new = st.next_arrival + lane
        slot = st.free[jnp.clip(st.n_free - 1 - lane, 0, s - 1)]
        tgt = jnp.where(valid, slot, s)  # s -> dropped by the scatters
        warm_i = jnp.asarray(warm, I32)
        ctl = ControllerState(
            tp_ewma=st.ctl.tp_ewma.at[tgt].set(0.0, mode="drop"),
            has_ewma=st.ctl.has_ewma.at[tgt].set(False, mode="drop"),
            current_split=st.ctl.current_split.at[tgt].set(
                warm_i, mode="drop"),
            pending_split=st.ctl.pending_split.at[tgt].set(
                PENDING_NONE, mode="drop"),
            pending_count=st.ctl.pending_count.at[tgt].set(0, mode="drop"),
            step=st.ctl.step.at[tgt].set(0, mode="drop"))
        ssched = st.sched._replace(
            avg_tp=st.sched.avg_tp.at[tgt].set(1.0, mode="drop"))
        lat = jnp.where(valid,
                        t - arrival_t[jnp.clip(sid_new, 0, m - 1)],
                        -1).astype(I32)
        new = st._replace(
            active=st.active.at[tgt].set(True, mode="drop"),
            sid=st.sid.at[tgt].set(sid_new, mode="drop"),
            age=st.age.at[tgt].set(0, mode="drop"),
            n_free=st.n_free - k,
            next_arrival=st.next_arrival + k,
            ctl=ctl, sched=ssched)
        return new, lat

    def _serve(st: PoolState, tables, est_t, true_t, cell_t):
        s = st.active.shape[0]
        act = st.active
        if tables.shape[0] == 1:  # shared lookup row (static at trace time)
            tab_t = jnp.broadcast_to(tables[0], (s, tables.shape[1]))
        else:
            tab_t = tables[jnp.clip(st.sid, 0, tables.shape[0] - 1)]
        if sched is None:
            share = act.astype(F32)  # informational; engine discards it
            eff_est = est_t
            new_ss = st.sched
        else:
            new_ss, share = scheduler_step(sched, n_cells, st.sched,
                                           cell_t, true_t, active=act)
            eff_est = est_t * share
        ctl, split = jax.vmap(step)(tab_t, st.ctl, eff_est)
        split = jnp.where(act, split, NO_SPLIT)
        return st._replace(ctl=ctl, sched=new_ss), split, share

    def _retire(st: PoolState, dwell):
        s = st.active.shape[0]
        m = dwell.shape[0]
        sidc = jnp.clip(st.sid, 0, m - 1)
        dep = st.active & (st.age + 1 >= dwell[sidc])
        n_dep = dep.sum(dtype=I32)
        pos = jnp.cumsum(dep.astype(I32)) - 1  # pack departures onto stack
        tgt = jnp.where(dep, st.n_free + pos, s)
        active = st.active & ~dep
        return st._replace(
            active=active,
            age=jnp.where(active, st.age + 1, st.age),
            free=st.free.at[tgt].set(jnp.arange(s, dtype=I32), mode="drop"),
            n_free=st.n_free + n_dep), n_dep

    def _gather_tp(st: PoolState, arr):
        m, el = arr.shape
        val = arr[jnp.clip(st.sid, 0, m - 1), jnp.clip(st.age, 0, el - 1)]
        return jnp.where(st.active, val.astype(F32), 0.0)

    def _serve_retire(st: PoolState, tables, est_t, true, cell, dwell):
        # one period's service of the admitted pool: the scheduler and the
        # controllers over the active slots, then the dwell-out retirement
        act, sid, age = st.active, st.sid, st.age
        true_t = _gather_tp(st, true)
        cell_t = cell[jnp.clip(sid, 0, cell.shape[0] - 1)]
        st, split, share = _serve(st, tables, est_t, true_t, cell_t)
        st, n_dep = _retire(st, dwell)
        return st, (act, sid, age, split, share, n_dep), true_t

    @jax.jit
    def admit(st, t, ready_end_t, arrival_t, warm):
        return _admit(st, t, ready_end_t, arrival_t, warm)

    @jax.jit
    def serve_retire(st, tables, est_t, true, cell, dwell):
        return _serve_retire(st, tables, est_t, true, cell, dwell)[:2]

    @jax.jit
    def gather(st, wins, iq, alloc, true):
        m = true.shape[0]
        el = true.shape[1]
        sidc = jnp.clip(st.sid, 0, m - 1)
        agec = jnp.clip(st.age, 0, el - 1)
        if wins.ndim == 4:  # (M, T, WINDOW, 15) precomputed windows
            k = wins[sidc, agec]
        else:  # fused featurize: (M, T + WINDOW, 15) normalized trace —
            # slot age a reads the trace span [a, a + WINDOW) directly,
            # so the windowed tensor is never materialized
            k = wins[sidc[:, None], agec[:, None]
                     + jnp.arange(WINDOW, dtype=I32)[None]]
        return (k, iq[sidc, agec], alloc[sidc],
                _gather_tp(st, true), st.active)

    @jax.jit
    def sweep(st0, tables, warm, est, true, cell, dwell, arrival_t,
              ready_end, ts0=None, dconst=None, dbytes=None):
        t_steps = ready_end.shape[0]

        def body(carry, xs):
            st, ts = carry
            t, ready_t = xs
            sid0 = st.next_arrival  # lanes admit sessions sid0, sid0+1, ...
            st, lat = _admit(st, t, ready_t, arrival_t, warm)
            est_t = _gather_tp(st, est)
            st, ys, true_t = _serve_retire(st, tables, est_t, true, cell,
                                           dwell)
            act, sid, age, split, share, n_dep = ys
            row = None
            if telem is not None:
                with jax.named_scope("telemetry_step"):
                    eff = None
                    if sched is not None:
                        # what split_metrics sees: PRB-scaled, floored
                        eff = jnp.maximum(true_t * jnp.clip(share, 0.0, 1.0),
                                          tpmod.PRB_FLOOR_MBPS)
                    ts, row = telmod.telemetry_step(
                        telem, ts, period=t, split=split, est_tp=est_t,
                        true_tp=true_t, eff_tp=eff, share=share, active=act,
                        dconst=dconst, dbytes=dbytes,
                        admit_sid=sid0 + jnp.arange(a_lanes, dtype=I32),
                        admit_lat=lat, n_depart=n_dep)
            return (st, ts), (act, sid, age, split, share, lat, n_dep, row)

        return lax.scan(body, (st0, ts0),
                        (jnp.arange(t_steps, dtype=I32), ready_end))

    return PoolPrograms(sweep=sweep, admit=admit, serve_retire=serve_retire,
                        gather=gather)


@dataclasses.dataclass
class LifecycleStats:
    """Per-slot lifecycle accounting of one churned run
    (``FleetResult.lifecycle``)."""

    capacity: int  # pool slots S
    n_sessions: int  # sessions in the admission FIFO
    n_admitted: int  # sessions admitted within the horizon
    occupancy: np.ndarray  # (T,) active slots per period
    admitted: np.ndarray  # (T,) admissions per period
    departed: np.ndarray  # (T,) departures per period
    admit_latency: np.ndarray  # (n_admitted,) periods queued, in
    # admission order — 0 means admitted the period it arrived

    @property
    def ue_steps(self) -> int:
        """Total slot-periods actually served (the churn benchmark's
        throughput numerator)."""
        return int(self.occupancy.sum())

    def p99_admit_latency(self) -> float:
        """99th-percentile admission queue time in periods."""
        if self.admit_latency.size == 0:
            return 0.0
        return float(np.percentile(self.admit_latency, 99))


def _pool_validate(sessions: EpisodeBatch, schedule: ChurnSchedule,
                   capacity: int, cell, sched) -> None:
    if capacity <= 0:
        raise ValueError(f"capacity must be positive: {capacity}")
    m = schedule.n_sessions
    if m == 0:
        raise ValueError("churn schedule has no sessions; raise the "
                         "arrival rate or the horizon")
    if sessions.n_ues != m:
        raise ValueError(
            f"episode has {sessions.n_ues} session rows but the schedule "
            f"has {m}; generate one episode row per scheduled session")
    if int(schedule.dwell.min(initial=1)) < 1:
        raise ValueError("session dwell times must be >= 1 period")
    if schedule.max_dwell > sessions.n_steps:
        raise ValueError(
            f"longest dwell ({schedule.max_dwell} periods) exceeds the "
            f"session trace length ({sessions.n_steps}); generate episodes "
            "with T >= ChurnConfig.max_dwell")
    if sched is not None:
        if cell is None:
            raise ValueError("a scheduler needs an (M,) per-session cell")
        if np.shape(cell) != (m,):
            raise ValueError(f"cell must be (M,) = ({m},): {np.shape(cell)}")


def _pool_tables(table, n_sessions: int) -> np.ndarray:
    if isinstance(table, StackedLookupTable):
        tables = np.asarray(table.tables)
        if tables.shape[0] != n_sessions:
            raise ValueError(
                f"stacked table has {tables.shape[0]} rows for "
                f"{n_sessions} sessions")
        return tables
    return np.asarray(table.table)[None]  # shared row, broadcast on device


def _pool_inputs(put, tables_np, warm_split, true_np, cell,
                 schedule: ChurnSchedule) -> tuple:
    """The per-session arrays both pool loops read, copied with ``put``:
    (tables, warm split, true tp, cell, dwell, arrival period)."""
    return (put(tables_np, I32), put(warm_split, I32), put(true_np, F32),
            put(cell if cell is not None else
                np.zeros(schedule.n_sessions), I32),
            put(schedule.dwell, I32), put(schedule.arrival_t, I32))


def simulate_pool(sessions: EpisodeBatch, schedule: ChurnSchedule, table,
                  profile: SplitProfile, cfg: ControllerConfig, *,
                  capacity: int, warm_split=None, estimator=None,
                  serving: Optional[ServingMesh] = None, online=None,
                  fixed_split: Optional[int] = None,
                  ue: DeviceProfile = UE_VM_2CORE,
                  server: DeviceProfile = EDGE_A40X2,
                  sched: Optional[SchedulerConfig] = None,
                  cell: Optional[np.ndarray] = None, n_cells: int = 1,
                  quant: Optional[str] = None, fused: bool = False,
                  telemetry: Optional[TelemetryConfig] = None):
    """Run a churning UE population through the slot pool.

    ``sessions``: an ``EpisodeBatch`` with one row per scheduled session —
    row ``i`` is session ``i``'s channel life, consumed from trace step 0
    at admission regardless of *when* the session is admitted (each
    session carries its own episode; the pool recycles slots, not
    traces). ``schedule``: the realised arrival/dwell process
    (``make_churn_schedule``). ``table`` may be shared or a
    ``StackedLookupTable`` with one row per *session*.

    The result is a ``FleetResult`` whose rows are the pool's ``capacity``
    slots over ``schedule.horizon`` periods: ``result.active`` marks
    occupancy (metrics are NaN and splits ``NO_SPLIT`` on empty cells),
    and ``result.lifecycle`` carries the admission/departure accounting.
    ``sched``/``estimator``/``online``/``fixed_split`` compose exactly as
    in ``simulate_fleet``; ``cell`` is a static (M,) per-session attach.
    ``quant``/``fused`` are the int8-serving / fused-featurize switches,
    forwarded to the frozen and online estimate paths (defaults are the
    exact prior program). ``telemetry``: a
    ``repro.sim.telemetry.TelemetryConfig`` carries the metric plane
    through the pool scan (per-lane admission events with queue latency,
    aggregate departures, masked histograms/stats) into
    ``FleetResult.telemetry``; ``telemetry=None`` (default) never builds
    it.
    """
    from repro.sim.engine import (FleetResult, _host_telemetry,
                                  estimate_fleet, split_metrics)

    _pool_validate(sessions, schedule, capacity, cell, sched)
    if online is not None and estimator is None:
        raise ValueError("online adaptation needs an estimator")
    m = schedule.n_sessions
    t_steps = schedule.horizon
    true_np = np.asarray(sessions.tp_mbps, float)  # (M, L)
    if warm_split is None:
        warm_split = cfg.fallback_split if fixed_split is None else fixed_split
    tables_np = _pool_tables(table, m)
    programs = pool_programs(cfg.ewma_alpha, cfg.hysteresis_steps,
                             cfg.fallback_split, sched, int(n_cells),
                             int(schedule.max_admits), telem=telemetry)
    tel, dconst, dbytes = _host_telemetry(telemetry, profile, ue, server)

    online_stats = None
    telem_rec = None
    if online is not None:
        with telmod.ingest() as put:
            inputs = _pool_inputs(put, tables_np, warm_split, true_np, cell,
                                  schedule)
        slots = _SlotLoop(programs, pool_init(capacity, warm_split),
                          schedule.ready_end, inputs, tel, dconst, dbytes,
                          sched is not None)
        est_tp, online_stats = _online_pool_run(
            sessions, estimator, online, slots, serving=serving, fused=fused,
            telemetry=tel)
        act_ts, sid_ts, age_ts, split_ts, share_ts, lat_ts, dep_ts = (
            slots.outputs())
        if tel is not None:
            telem_rec = tel.decode()
    else:
        est_np = (estimate_fleet(sessions, estimator, serving=serving,
                                 quant=quant, fused=fused)
                  if estimator is not None else true_np)
        with telmod.stage("controller"):
            st0 = pool_init(capacity, warm_split)
            with telmod.ingest() as put:
                (tables_d, warm_d, true_d, cell_d, dwell_d,
                 arrival_d) = _pool_inputs(put, tables_np, warm_split,
                                           true_np, cell, schedule)
                est_d = put(est_np, F32)
                ready_d = put(schedule.ready_end, I32)
            (_, ts), ys = programs.sweep(
                st0, tables_d, warm_d, est_d, true_d, cell_d, dwell_d,
                arrival_d, ready_d, tel.ts if tel is not None else None,
                dconst, dbytes)
            if tel is not None:
                tel.ts = ts
                telem_rec = tel.decode(ys[7])
            act_ts, sid_ts, age_ts, split_ts, share_ts, lat_ts, dep_ts = (
                np.asarray(y) for y in ys[:7])
        est_tp = None  # gathered below from the per-session estimates

    with telmod.stage("gather"):
        act = act_ts.T  # (S, T)
        sid = np.clip(sid_ts.T, 0, m - 1)
        age = np.clip(age_ts.T, 0, sessions.n_steps - 1)
        splits = split_ts.T.astype(np.int32)
        true_tp = np.where(act, true_np[sid, age], 0.0)
        if est_tp is None:
            est_src = est_np if estimator is not None else true_np
            est_tp = np.where(act, np.asarray(est_src, float)[sid, age], 0.0)
        shares = None
        if sched is not None:
            shares = np.where(act, share_ts.T, 0.0)
            eff_tp = tpmod.prb_scaled_mbps(true_tp, shares)
            est_tp = est_tp * shares  # what the controllers consumed
        else:
            eff_tp = true_tp

    def _metrics(l):
        d, p, e = split_metrics(profile, np.where(act, l, 0), eff_tp,
                                ue, server)
        nan = np.nan
        return (np.where(act, d, nan), np.where(act, p, nan),
                np.where(act, e, nan))

    with telmod.stage("split_metrics"):
        delay, priv, energy = _metrics(splits)
        fixed = None
        if fixed_split is not None:
            fsplits = np.where(act, fixed_split, NO_SPLIT).astype(np.int32)
            fd, fp, fe = _metrics(fsplits)
            fixed = FleetResult(fsplits, true_tp, est_tp, fd, fp, fe,
                                prb_share=shares, active=act)
    lat_valid = lat_ts >= 0
    stats = LifecycleStats(
        capacity=int(capacity), n_sessions=m,
        n_admitted=int(lat_valid.sum()),
        occupancy=act_ts.sum(axis=1).astype(np.int64),
        admitted=lat_valid.sum(axis=1).astype(np.int64),
        departed=dep_ts.astype(np.int64),
        admit_latency=lat_ts[lat_valid].astype(np.int64))
    return FleetResult(splits, true_tp, est_tp, delay, priv, energy, fixed,
                       prb_share=shares, online=online_stats, active=act,
                       lifecycle=stats, telemetry=telem_rec)


@jax.jit
def _ssm_slot_reset(state, warm_all, sid, fresh):
    # scatter-free state reset: freshly admitted slots (active with age 0,
    # i.e. admitted this period) take their session's precomputed warmed
    # state; everyone else keeps the state they carried
    w = warm_all[jnp.clip(sid, 0, warm_all.shape[0] - 1)]
    return jnp.where(fresh.reshape(fresh.shape + (1,) * (w.ndim - 1)),
                     w, state)


@jax.jit
def _ssm_pool_gather(active, sid, age, feats, true):
    # each active slot's current report: session trace column
    # WINDOW - 1 + age (the warmup prefix was consumed at admission by
    # the precomputed warm state), plus the period's measured label
    m, l = true.shape
    sidc = jnp.clip(sid, 0, m - 1)
    agec = jnp.clip(age, 0, l - 1)
    f = feats[sidc, agec + (WINDOW - 1)]
    tp = jnp.where(active, true[sidc, agec].astype(F32), 0.0)
    return f, tp


class _SlotLoop:
    """The slot bookkeeping of the online pool loops: per period,
    ``admit``, then (after the loop's estimate) ``serve`` through the
    jitted ``serve_retire`` with the host telemetry update, and at the end
    the stacked per-period ``outputs`` in the sweep's order."""

    def __init__(self, programs: PoolPrograms, st0: PoolState, ready,
                 inputs: tuple, telemetry=None, tel_dconst=None,
                 tel_dbytes=None, tel_sched: bool = False):
        self.programs, self.st = programs, st0
        self.ready = np.asarray(ready, np.int64)
        (self.tables_d, self.warm_d, self.true_d, self.cell_d, self.dwell_d,
         self.arrival_d) = inputs
        self.telemetry, self.tel_sched = telemetry, tel_sched
        self.tel_dconst, self.tel_dbytes = tel_dconst, tel_dbytes
        self.outs: list = []
        self.lat_rows: list = []
        self.sid0 = 0

    def admit(self, t: int) -> PoolState:
        if self.telemetry is not None:
            self.sid0 = int(self.st.next_arrival)
        self.st, lat = self.programs.admit(
            self.st, jnp.asarray(t, I32), jnp.asarray(int(self.ready[t]), I32),
            self.arrival_d, self.warm_d)
        self.lat_rows.append(np.asarray(lat))
        return self.st

    def serve(self, t: int, est_col, tp_np) -> None:
        self.st, ys = self.programs.serve_retire(
            self.st, self.tables_d, jnp.asarray(est_col, F32), self.true_d,
            self.cell_d, self.dwell_d)
        o = [np.asarray(y) for y in ys]
        self.outs.append(o)
        if self.telemetry is not None:
            eff = (np.maximum(tp_np * np.clip(o[4], 0.0, 1.0),
                              tpmod.PRB_FLOOR_MBPS)
                   if self.tel_sched else None)
            lat = self.lat_rows[-1]
            self.telemetry.update(
                period=t, split=o[3], est=est_col, true=tp_np, share=o[4],
                active=o[0], dconst=self.tel_dconst, dbytes=self.tel_dbytes,
                eff=eff, admit_sid=self.sid0 + np.arange(lat.shape[0]),
                admit_lat=lat, n_depart=o[5])

    def outputs(self) -> tuple:
        act, sid, age, split, share, dep = (
            np.stack([o[i] for o in self.outs]) for i in range(6))
        return act, sid, age, split, share, np.stack(self.lat_rows), dep


def _check_ring_room(ocfg, slots: _SlotLoop) -> int:
    s_slots = int(slots.st.active.shape[0])
    if int(ocfg.capacity) < s_slots:
        raise ValueError(
            f"OnlineConfig.capacity ({ocfg.capacity}) must cover the pool "
            f"capacity ({s_slots}) for masked ingestion")
    return s_slots


def _online_pool_run(sessions, estimator, ocfg, slots: _SlotLoop, *,
                     serving=None, tp_clip=TP_CLIP_MBPS, fused=False,
                     telemetry=None):
    """The closed-loop arm of ``simulate_pool``: the same admit/serve/
    retire step driven from a host loop (``slots``) so each period's
    estimator forward runs with the *current* weights, only active slots'
    samples are ring-ingested (``buffer_add_masked``), and drift-triggered
    adaptation bursts run between periods (``sim.online.OnlineAdapter``,
    shared with ``online_estimate_fleet``). Returns the (S, T) estimates
    and the ``OnlineStats``.

    ``telemetry``: an optional ``telemetry.HostTelemetry`` — per period
    one jitted metric update (masked to the live slots, with admission
    lanes and departures; ``slots`` makes it) plus drift/burst/weight-swap
    events; the caller decodes the record."""
    from repro.dist import sharding as sh
    from repro.estimator.ssm import SSMConfig
    from repro.sim.online import OnlineAdapter, buffer_add_masked

    if isinstance(estimator[0], SSMConfig):
        return _online_pool_run_ssm(sessions, estimator, ocfg, slots,
                                    serving=serving, tp_clip=tp_clip,
                                    telemetry=telemetry)
    if sessions.iq is None:
        raise ValueError(
            "online adaptation needs IQ spectrograms: generate the episode "
            "with include_iq=True")
    s_slots = _check_ring_room(ocfg, slots)
    t_steps = len(slots.ready)
    if fused:
        # normalized trace instead of the WINDOW x window tensor; the
        # pool gather windows it per slot age (bit-identical elements)
        from repro.channel import kpm as kpmmod
        if sessions.kpms is None:
            raise ValueError("fused featurize needs raw KPM reports: "
                             "generate sessions with include_kpms=True")
        wins_d = jnp.asarray(
            kpmmod.normalize_kpms(sessions.kpms).astype(np.float32))
    else:
        wins_d = jnp.asarray(
            sessions.kpm_windows(normalize=True).astype(np.float32))
    iq_d = jnp.asarray(np.asarray(sessions.iq, np.float32))
    alloc_d = jnp.asarray(sessions.alloc_ratio.astype(np.float32))
    ad = OnlineAdapter(estimator, ocfg, t_steps, serving=serving,
                       telemetry=telemetry)
    est_tp = np.zeros((s_slots, t_steps))
    with ad.rules():
        for t in range(t_steps):
            st = slots.admit(t)
            kpms_t, iq_t, alloc_t, tp_t, act_m = slots.programs.gather(
                st, wins_d, iq_d, alloc_d, slots.true_d)
            if serving is not None:
                kpms_t = sh.put(kpms_t, ("batch", None, None))
                iq_t = sh.put(iq_t, ("batch", None, None, None))
                alloc_t = sh.put(alloc_t, ("batch",))
                tp_t = sh.put(tp_t, ("batch",))
            with telmod.stage("estimator_fwd"):
                raw = np.asarray(ad.predict(ad.params, kpms_t, iq_t,
                                            alloc_t))
            act_np = np.asarray(act_m)
            est_col = np.where(act_np,
                               np.clip(raw, tp_clip[0], tp_clip[1]), 0.0)
            est_tp[:, t] = est_col
            tp_np = np.asarray(tp_t)
            n_act = max(int(act_np.sum()), 1)
            ad.add(buffer_add_masked, kpms_t, iq_t, alloc_t, tp_t, act_m)
            ad.observe(t, float(np.sqrt(
                np.sum(act_np * (est_col - tp_np) ** 2) / n_act)))
            slots.serve(t, est_col, tp_np)
    return est_tp, ad.finish()


def _online_pool_run_ssm(sessions, estimator, ocfg, slots: _SlotLoop, *,
                         serving=None, tp_clip=TP_CLIP_MBPS, telemetry=None):
    """The recurrent closed-loop arm of ``simulate_pool``.

    Slots carry per-slot SSD states alongside the controller states. On
    admission a slot's state is reset to its session's *warmed* state —
    ``ssm_warm_state`` over the trace's WINDOW - 1 warmup reports,
    precomputed for every session in one sequence pass and recomputed
    after each adaptation burst so later admits warm with the weights
    that will serve them (bursts are rare; live slots are NOT re-warmed —
    the recurrence forgets old-weight history at its trained decay, see
    ``sim.online._online_estimate_fleet_ssm``). Each period is then one
    O(1) ``ssm_step`` over the capacity axis, masked ring-ingest of
    (pre-report state, report, label) events, and the shared drift/burst
    machinery (``sim.online.OnlineAdapter``)."""
    from repro.dist import sharding as sh
    from repro.estimator.ssm import (episode_features, reduce_forecasts,
                                     ssm_state_init, ssm_warm_state)
    from repro.sim.online import OnlineAdapter, _place, buffer_add_ssm
    from repro.sim.serving import STATE_AXES

    c = estimator[0]
    if sessions.kpms is None:
        raise ValueError("the recurrent estimator needs raw KPM reports: "
                         "generate sessions with include_kpms=True")
    if c.include_iq and sessions.iq is None:
        raise ValueError("SSMConfig(include_iq=True) needs spectrogram "
                         "snapshots: generate sessions with "
                         "include_iq=True")
    s_slots = _check_ring_room(ocfg, slots)
    t_steps = len(slots.ready)
    feats_np = episode_features(sessions.kpms, sessions.alloc_ratio,
                                sessions.iq if c.include_iq else None)
    feats_d = jnp.asarray(feats_np)  # (M, L + WINDOW, F)
    warm_prefix = jnp.asarray(feats_np[:, :WINDOW - 1])
    ad = OnlineAdapter(estimator, ocfg, t_steps, serving=serving,
                       telemetry=telemetry)
    est_tp = np.zeros((s_slots, t_steps))
    with ad.rules():
        warm_all = ssm_warm_state(c, ad.params, warm_prefix)  # (M, ...)
        slot_state = _place(ssm_state_init(c, (s_slots,)), STATE_AXES)
        for t in range(t_steps):
            st = slots.admit(t)
            fresh = st.active & (st.age == 0)  # admitted this period
            slot_state = _ssm_slot_reset(slot_state, warm_all, st.sid,
                                         fresh)
            feats_t, tp_t = _ssm_pool_gather(st.active, st.sid, st.age,
                                             feats_d, slots.true_d)
            if serving is not None:
                slot_state = sh.put(slot_state, STATE_AXES)
                feats_t = _place(feats_t, ("batch", None))
                tp_t = _place(tp_t, ("batch",))
            state_prev = slot_state
            with telmod.stage("estimator_fwd"):
                slot_state, fc = ad.predict(ad.params, slot_state, feats_t)
                fc = np.asarray(fc)
            act_np = np.asarray(st.active)
            cur = np.clip(fc[:, 0], tp_clip[0], tp_clip[1])
            est_col = np.where(
                act_np, np.clip(reduce_forecasts(c, fc),
                                tp_clip[0], tp_clip[1]), 0.0)
            est_tp[:, t] = est_col
            tp_np = np.asarray(tp_t)
            n_act = max(int(act_np.sum()), 1)
            ad.add(buffer_add_ssm, state_prev, feats_t, tp_t,
                   mask=st.active)
            if ad.observe(t, float(np.sqrt(
                    np.sum(act_np * (cur - tp_np) ** 2) / n_act))):
                # future admits warm with the weights that will serve them
                warm_all = ssm_warm_state(c, ad.params, warm_prefix)
            slots.serve(t, est_col, tp_np)
    return est_tp, ad.finish()
