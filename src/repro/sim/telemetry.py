"""Fleet telemetry: in-scan metrics, a device event log, stage tracing.

The fleet engine reacts to signals — split decisions, estimate error,
drift triggers, admission churn — that until now were only visible as
coarse per-run ``FleetResult`` arrays. This module makes per-period
fleet health a first-class, device-resident plane:

  * :class:`TelemetryState` — pure-jnp counters, running mean/min/max
    channels and fixed-bucket histograms (split index, estimate error,
    E2E delay, PRB share, occupancy), advanced by
    :func:`telemetry_step` *inside* the engine/pool ``lax.scan`` with
    mask-aware reductions: inactive slots are redirected to a dummy
    histogram bucket that ``mode="drop"`` discards, so one compiled
    update serves every occupancy level and nothing syncs to the host
    until the run ends;
  * :class:`EventRing` — a fixed-capacity device log of typed events
    (admission, departure, handover, drift trigger/recovery, online
    burst start/end, serving weight swap) with period stamps, written
    with the replay-ring scatter idiom. The ring keeps the *first*
    ``events_capacity`` events and counts the rest in ``dropped`` — it
    never overflows silently;
  * :func:`telemetry_decode` — the one host sync: state + per-period
    rows -> a :class:`TelemetryRecord` of plain numpy/dataclass fields
    with JSON-lines and Prometheus-text exporters
    (:func:`to_jsonl`, :func:`to_prometheus`);
  * :func:`stage` / :func:`ingest` / :func:`trace_capture` —
    ``jax.named_scope`` + ``jax.profiler.TraceAnnotation`` spans around
    the report-period stages, with event stats (the bytes each
    host-to-device copy moved) riding on them; :func:`timed` is the
    best/median/spread timer behind ``benchmarks/fleet.py --profile``.

``simulate_fleet(telemetry=None)`` (the default) never builds any of
this — the traced programs are bit-identical to the prior engine,
pinned by ``tests/test_sim_telemetry.py``.

In-scan delay is recomputed in f32 from the same formula as
``engine.split_metrics`` (profile delay constants + bytes over floored
throughput); it feeds histograms and running stats, not the f64
``FleetResult.delay_s`` arrays, so the histogram-grade precision is
deliberate.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
I32 = jnp.int32

# mirrors engine.TP_FLOOR_BPS / channel.throughput.PRB_FLOOR_MBPS (kept
# literal here: telemetry sits below the engine in the import graph)
_TP_FLOOR_BPS = 1.0
_PRB_FLOOR_MBPS = 0.01

# ------------------------------------------------------------- event kinds
EV_ADMIT = 1  # arg = session id, val = admission latency (periods queued)
EV_DEPART = 2  # arg = departures this period
EV_HANDOVER = 3  # arg = UEs whose cell index changed this period
EV_DRIFT_TRIGGER = 4  # arg = trigger ordinal, val = period RMSE (Mbps)
EV_DRIFT_RECOVER = 5  # val = period RMSE back under the threshold
EV_BURST_START = 6  # arg = scheduled AdamW steps
EV_BURST_END = 7  # arg = steps run, val = mean minibatch loss
EV_WEIGHT_SWAP = 8  # serving-mesh weight refresh after a burst

EVENT_NAMES = {EV_ADMIT: "admit", EV_DEPART: "depart",
               EV_HANDOVER: "handover", EV_DRIFT_TRIGGER: "drift_trigger",
               EV_DRIFT_RECOVER: "drift_recover",
               EV_BURST_START: "burst_start", EV_BURST_END: "burst_end",
               EV_WEIGHT_SWAP: "weight_swap"}

# ------------------------------------------------------------ stat channels
STAT_ERR = 0  # |est - true| full-grant estimate error (Mbps), per slot
STAT_DELAY = 1  # E2E delay at the deployed split (s), per slot
STAT_SHARE = 2  # granted PRB share, per slot
STAT_EST = 3  # estimate fed to the controller (Mbps), per slot
STAT_TRUE = 4  # measured throughput (Mbps), per slot
STAT_OCC = 5  # active slots, one sample per period
N_STATS = 6
STAT_NAMES = ("err_abs_mbps", "delay_s", "prb_share", "est_tp_mbps",
              "true_tp_mbps", "occupancy")


@dataclasses.dataclass(frozen=True)
class TelemetryConfig:
    """Static telemetry knobs (frozen + hashable: keys the program caches).

    Histogram ranges clip into the last bucket, so out-of-range samples
    are counted, never lost. ``split_bins`` buckets are *split index + 1*
    — bucket 0 holds ``NO_SPLIT`` decisions — and must cover the
    profile's layer count + 1. ``trace_dir`` opts into a
    ``jax.profiler.trace`` capture around the run (default off: the
    profiler is for humans, the metric plane is always-on)."""

    split_bins: int = 48  # split index + 1 (bucket 0 = NO_SPLIT)
    err_bins: int = 32
    err_max_mbps: float = 40.0  # ~ the PSO TP_CLIP sweep range
    delay_bins: int = 32
    delay_max_s: float = 2.0
    share_bins: int = 16  # PRB share in [0, 1]
    occ_bins: int = 16  # occupancy fraction in [0, 1]
    events_capacity: int = 4096
    trace_dir: Optional[str] = None

    def __post_init__(self):
        for f in ("split_bins", "err_bins", "delay_bins", "share_bins",
                  "occ_bins", "events_capacity"):
            if int(getattr(self, f)) <= 0:
                raise ValueError(f"{f} must be positive: {getattr(self, f)}")
        if self.err_max_mbps <= 0 or self.delay_max_s <= 0:
            raise ValueError("histogram ranges must be positive")


class EventRing(NamedTuple):
    """Fixed-capacity device event log (first-``C`` kept, rest counted).

    Unlike the replay ring, old events are never overwritten: a debugging
    timeline must keep its *head* (the drift trigger matters more than
    the 4000th admission after it). ``dropped`` counts what didn't fit —
    the overflow is loud, never silent."""

    kind: jax.Array  # (C,) i32 EV_* codes
    period: jax.Array  # (C,) i32 report period of the event
    arg: jax.Array  # (C,) i32 integer payload (sid / count / steps)
    val: jax.Array  # (C,) f32 float payload (latency / rmse / loss)
    count: jax.Array  # i32 scalar — events stored
    dropped: jax.Array  # i32 scalar — events that found the ring full


class TelemetryState(NamedTuple):
    """The device-resident metric plane carried through the scan."""

    periods: jax.Array  # i32 — report periods observed
    active_steps: jax.Array  # i32 — live (slot, period) samples
    admitted: jax.Array  # i32 — admissions recorded
    departed: jax.Array  # i32 — departures recorded
    handovers: jax.Array  # i32 — cell-index changes recorded
    split_hist: jax.Array  # (split_bins,) i32
    err_hist: jax.Array  # (err_bins,) i32
    delay_hist: jax.Array  # (delay_bins,) i32
    share_hist: jax.Array  # (share_bins,) i32
    occ_hist: jax.Array  # (occ_bins,) i32 — one sample per period
    sums: jax.Array  # (N_STATS,) f32 running sums
    mins: jax.Array  # (N_STATS,) f32 running minima (+inf when empty)
    maxs: jax.Array  # (N_STATS,) f32 running maxima (-inf when empty)
    events: EventRing


class TelemetryRow(NamedTuple):
    """One period's time-series row (stacked by the scan into (T,) ys)."""

    n_active: jax.Array  # i32
    err_sq_sum: jax.Array  # f32 — sum over active slots of (est - true)^2
    delay_sum: jax.Array  # f32 — sum over active slots of delay_s
    admitted: jax.Array  # i32
    departed: jax.Array  # i32


def ring_init(capacity: int) -> EventRing:
    c = int(capacity)
    return EventRing(kind=jnp.zeros((c,), I32), period=jnp.zeros((c,), I32),
                     arg=jnp.zeros((c,), I32), val=jnp.zeros((c,), F32),
                     count=jnp.zeros((), I32), dropped=jnp.zeros((), I32))


def telemetry_init(cfg: TelemetryConfig) -> TelemetryState:
    """An empty metric plane for one run (all leaves device arrays)."""
    zero = jnp.zeros((), I32)
    return TelemetryState(
        periods=zero, active_steps=zero, admitted=zero, departed=zero,
        handovers=zero,
        split_hist=jnp.zeros((cfg.split_bins,), I32),
        err_hist=jnp.zeros((cfg.err_bins,), I32),
        delay_hist=jnp.zeros((cfg.delay_bins,), I32),
        share_hist=jnp.zeros((cfg.share_bins,), I32),
        occ_hist=jnp.zeros((cfg.occ_bins,), I32),
        sums=jnp.zeros((N_STATS,), F32),
        mins=jnp.full((N_STATS,), jnp.inf, F32),
        maxs=jnp.full((N_STATS,), -jnp.inf, F32),
        events=ring_init(cfg.events_capacity))


def ring_push(ring: EventRing, kind, period, arg, val, valid) -> EventRing:
    """Append up to K events (the valid lanes) to the log, in lane order.

    All args are (K,) arrays (scalars broadcast by the caller). The write
    is the replay-ring cumsum-packed scatter: each valid lane takes the
    next free index, lanes past capacity and invalid lanes scatter to
    index ``C`` which ``mode="drop"`` discards. Keep-first semantics:
    overflow increments ``dropped`` instead of overwriting."""
    cap = ring.kind.shape[0]
    valid = jnp.asarray(valid, bool)
    v = valid.astype(I32)
    slot = ring.count + jnp.cumsum(v) - v  # index each valid lane takes
    ok = valid & (slot < cap)
    tgt = jnp.where(ok, slot, cap)
    stored = ok.sum(dtype=I32)
    return EventRing(
        kind=ring.kind.at[tgt].set(jnp.asarray(kind, I32), mode="drop"),
        period=ring.period.at[tgt].set(jnp.asarray(period, I32),
                                       mode="drop"),
        arg=ring.arg.at[tgt].set(jnp.asarray(arg, I32), mode="drop"),
        val=ring.val.at[tgt].set(jnp.asarray(val, F32), mode="drop"),
        count=ring.count + stored,
        dropped=ring.dropped + v.sum(dtype=I32) - stored)


def _bucket(x, scale, bins: int):
    """Linear bucket index into [0, bins): clips into the edge buckets."""
    return jnp.clip((x * scale).astype(I32), 0, bins - 1)


def _masked_hist(hist, bucket, active):
    """Add 1 per active row to its bucket via a one-hot compare-reduce
    (the ``kernels/segsum`` idiom: bins x S comparisons vectorize where an
    XLA CPU scatter serializes, ~4x faster at S=1024). Inactive rows match
    no bucket — histogram totals therefore equal the active-sample count
    exactly."""
    bins = hist.shape[0]
    oh = (bucket[None, :] == jnp.arange(bins, dtype=bucket.dtype)[:, None])
    return hist + (oh & active[None, :]).sum(axis=1, dtype=hist.dtype)


def telemetry_step(cfg: TelemetryConfig, ts: TelemetryState, *, period,
                   split, est_tp, true_tp, share, active, dconst, dbytes,
                   eff_tp=None, admit_sid=None, admit_lat=None,
                   n_depart=None, n_handover=None
                   ) -> tuple[TelemetryState, TelemetryRow]:
    """Fold one report period into the metric plane (pure jnp, scan-safe).

    ``split``/``est_tp``/``true_tp``/``share``/``active``: (S,) per-slot
    arrays as the engine carries them (``split`` may be ``NO_SPLIT``;
    inactive rows contribute to nothing). ``dconst``/``dbytes``: the
    (L,) per-split delay constants (``d_ue + d_ser``) and boundary bytes
    of the run's profile. ``eff_tp`` is the PRB-scaled served throughput
    driving the delay metric (defaults to ``true_tp`` on uncontended
    paths). Event inputs are optional: ``admit_lat`` lanes with latency
    >= 0 log EV_ADMIT events (``admit_sid`` carries the session ids),
    positive ``n_depart``/``n_handover`` log one aggregate event each.
    """
    period = jnp.asarray(period, I32)
    active = jnp.asarray(active, bool)
    actf = active.astype(F32)
    n_act = active.sum(dtype=I32)
    est = jnp.asarray(est_tp, F32)
    true = jnp.asarray(true_tp, F32)
    share = jnp.asarray(share, F32)
    eff = true if eff_tp is None else jnp.asarray(eff_tp, F32)

    err = jnp.abs(est - true)
    nl = dconst.shape[0]
    li = jnp.clip(jnp.asarray(split, I32), 0, nl - 1)
    delay = dconst[li] + dbytes[li] * 8.0 / jnp.maximum(eff * 1e6,
                                                        _TP_FLOOR_BPS)

    # histograms (masked: totals == active samples)
    split_b = jnp.clip(jnp.asarray(split, I32) + 1, 0, cfg.split_bins - 1)
    err_b = _bucket(err, cfg.err_bins / cfg.err_max_mbps, cfg.err_bins)
    delay_b = _bucket(delay, cfg.delay_bins / cfg.delay_max_s,
                      cfg.delay_bins)
    share_b = _bucket(share, float(cfg.share_bins), cfg.share_bins)
    occ_frac = n_act.astype(F32) / active.shape[0]
    occ_b = _bucket(occ_frac[None], float(cfg.occ_bins), cfg.occ_bins)

    # running sum/min/max per stat channel, inactive rows neutralized
    samples = jnp.stack([err, delay, share, est, true])  # (5, S)
    sums5 = (samples * actf).sum(axis=1)
    mins5 = jnp.where(active, samples, jnp.inf).min(axis=1)
    maxs5 = jnp.where(active, samples, -jnp.inf).max(axis=1)
    occf = n_act.astype(F32)
    sums = ts.sums + jnp.concatenate([sums5, occf[None]])
    mins = jnp.minimum(ts.mins, jnp.concatenate([mins5, occf[None]]))
    maxs = jnp.maximum(ts.maxs, jnp.concatenate([maxs5, occf[None]]))

    events = ts.events
    admitted = jnp.zeros((), I32)
    if admit_lat is not None:
        lat = jnp.asarray(admit_lat, I32)
        ok = lat >= 0
        admitted = ok.sum(dtype=I32)
        sid = (jnp.zeros_like(lat) if admit_sid is None
               else jnp.asarray(admit_sid, I32))
        events = ring_push(events, jnp.full_like(lat, EV_ADMIT),
                           jnp.full_like(lat, period), sid,
                           lat.astype(F32), ok)
    departed = jnp.zeros((), I32)
    if n_depart is not None:
        departed = jnp.asarray(n_depart, I32)
        events = ring_push(events, jnp.asarray([EV_DEPART], I32),
                           period[None], departed[None],
                           jnp.zeros((1,), F32), (departed > 0)[None])
    handovers = jnp.zeros((), I32)
    if n_handover is not None:
        handovers = jnp.asarray(n_handover, I32)
        events = ring_push(events, jnp.asarray([EV_HANDOVER], I32),
                           period[None], handovers[None],
                           jnp.zeros((1,), F32), (handovers > 0)[None])

    new = TelemetryState(
        periods=ts.periods + 1,
        active_steps=ts.active_steps + n_act,
        admitted=ts.admitted + admitted,
        departed=ts.departed + departed,
        handovers=ts.handovers + handovers,
        split_hist=_masked_hist(ts.split_hist, split_b, active),
        err_hist=_masked_hist(ts.err_hist, err_b, active),
        delay_hist=_masked_hist(ts.delay_hist, delay_b, active),
        share_hist=_masked_hist(ts.share_hist, share_b, active),
        occ_hist=ts.occ_hist.at[occ_b].add(1),
        sums=sums, mins=mins, maxs=maxs, events=events)
    row = TelemetryRow(
        n_active=n_act,
        err_sq_sum=((est - true) ** 2 * actf).sum(),
        delay_sum=(delay * actf).sum(),
        admitted=admitted, departed=departed)
    return new, row


# ------------------------------------------------- host-loop companion
@jax.jit
def _push_one(ts: TelemetryState, kind, period, arg, val) -> TelemetryState:
    ring = ring_push(ts.events, kind[None], period[None], arg[None],
                     val[None], jnp.ones((1,), bool))
    return ts._replace(events=ring)


@functools.lru_cache(maxsize=None)
def _update_program(cfg: TelemetryConfig):
    """One jitted metric update per config for host-driven loops (the
    online paths): compiled once, reused every period at any occupancy."""

    @jax.jit
    def update(ts, period, split, est, true, eff, share, active, dconst,
               dbytes, admit_sid, admit_lat, n_depart):
        return telemetry_step(cfg, ts, period=period, split=split,
                              est_tp=est, true_tp=true, eff_tp=eff,
                              share=share, active=active, dconst=dconst,
                              dbytes=dbytes, admit_sid=admit_sid,
                              admit_lat=admit_lat, n_depart=n_depart)

    return update


class HostTelemetry:
    """The metric plane for host-driven period loops (the online paths).

    Wraps a device :class:`TelemetryState` with per-period jitted metric
    updates, host event pushes and drift-edge tracking, so the four
    online loops share one telemetry idiom. Everything stays on device;
    :meth:`decode` is the single host sync."""

    def __init__(self, cfg: TelemetryConfig,
                 ts: Optional[TelemetryState] = None):
        self.cfg = cfg
        self.ts = telemetry_init(cfg) if ts is None else ts
        self.rows: list = []
        self._in_drift = False

    def update(self, *, period, split, est, true, share, active, dconst,
               dbytes, eff=None, admit_sid=None, admit_lat=None,
               n_depart=0):
        s = np.shape(active)[0]
        if admit_lat is None:
            admit_sid, admit_lat = (jnp.zeros((1,), I32),
                                    -jnp.ones((1,), I32))
        self.ts, row = _update_program(self.cfg)(
            self.ts, jnp.asarray(period, I32), jnp.asarray(split, I32),
            jnp.asarray(est, F32), jnp.asarray(true, F32),
            jnp.asarray(true if eff is None else eff, F32),
            jnp.asarray(share, F32) if np.ndim(share) else
            jnp.full((s,), share, F32),
            jnp.asarray(active, bool), dconst, dbytes,
            jnp.asarray(admit_sid, I32), jnp.asarray(admit_lat, I32),
            jnp.asarray(n_depart, I32))
        self.rows.append(row)

    def event(self, kind: int, period: int, arg: int = 0, val: float = 0.0):
        self.ts = _push_one(self.ts, jnp.asarray(kind, I32),
                            jnp.asarray(period, I32), jnp.asarray(arg, I32),
                            jnp.asarray(val, F32))

    def drift(self, period: int, fired: bool, rmse: float,
              threshold: float, n_triggers: int = 0):
        """Feed the period's monitor outcome; logs trigger/recovery edges
        (recovery = first post-trigger period back under the threshold)."""
        if fired:
            self._in_drift = True
            self.event(EV_DRIFT_TRIGGER, period, arg=n_triggers, val=rmse)
        elif self._in_drift and rmse <= threshold:
            self._in_drift = False
            self.event(EV_DRIFT_RECOVER, period, val=rmse)

    def burst(self, period: int, steps: int, loss: float, swapped: bool):
        self.event(EV_BURST_START, period, arg=steps)
        self.event(EV_BURST_END, period, arg=steps, val=loss)
        if swapped:
            self.event(EV_WEIGHT_SWAP, period)

    def decode(self, rows=None) -> "TelemetryRecord":
        return telemetry_decode(self.cfg, self.ts,
                                rows if rows is not None else self.rows)


# ----------------------------------------------------------- host decode
@dataclasses.dataclass
class TelemetryEvent:
    """One decoded event (host side of the :class:`EventRing`)."""

    kind: str
    period: int
    arg: int
    value: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class TelemetryRecord:
    """The decoded metric plane of one run (``FleetResult.telemetry``)."""

    periods: int
    active_steps: int
    admitted: int
    departed: int
    handovers: int
    stats: dict  # name -> {mean, min, max}
    hists: dict  # name -> {edges: [b+1 floats], counts: [b ints]}
    series: dict  # name -> (T,) list (occupancy / rmse / mean_delay_s /
    # admitted / departed); empty when no per-period rows were kept
    events: list  # [TelemetryEvent] in period order
    dropped_events: int

    def event_timeline(self, kinds: Optional[Sequence[str]] = None) -> list:
        """Events filtered to ``kinds`` (default: all), period order."""
        return [e for e in self.events if kinds is None or e.kind in kinds]

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["events"] = [e.to_dict() for e in self.events]
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TelemetryRecord":
        d = dict(d)
        d["events"] = [TelemetryEvent(**e) for e in d.get("events", [])]
        return cls(**d)


def _edges(lo: float, hi: float, bins: int) -> list:
    return [lo + (hi - lo) * i / bins for i in range(bins + 1)]


def _stack_rows(rows) -> Optional[TelemetryRow]:
    if rows is None:
        return None
    if isinstance(rows, TelemetryRow):  # scan ys: already stacked (T,)
        return rows
    if len(rows) == 0:
        return None
    return TelemetryRow(*(np.asarray(x) for x in zip(*rows)))


def telemetry_decode(cfg: TelemetryConfig, ts: TelemetryState, rows=None
                     ) -> TelemetryRecord:
    """Device state (+ optional per-period rows) -> host record. The one
    host sync of the telemetry plane; everything before it is jnp."""
    periods = int(ts.periods)
    active_steps = int(ts.active_steps)
    sums = np.asarray(ts.sums, float)
    mins = np.asarray(ts.mins, float)
    maxs = np.asarray(ts.maxs, float)
    denom = np.array([max(active_steps, 1)] * (N_STATS - 1)
                     + [max(periods, 1)], float)
    seen = np.array([active_steps] * (N_STATS - 1) + [periods]) > 0
    stats = {name: {"mean": float(sums[i] / denom[i]) if seen[i] else 0.0,
                    "min": float(mins[i]) if seen[i] else 0.0,
                    "max": float(maxs[i]) if seen[i] else 0.0}
             for i, name in enumerate(STAT_NAMES)}
    hists = {
        "split": {"edges": _edges(-1, cfg.split_bins - 1, cfg.split_bins),
                  "counts": np.asarray(ts.split_hist).tolist()},
        "err_mbps": {"edges": _edges(0, cfg.err_max_mbps, cfg.err_bins),
                     "counts": np.asarray(ts.err_hist).tolist()},
        "delay_s": {"edges": _edges(0, cfg.delay_max_s, cfg.delay_bins),
                    "counts": np.asarray(ts.delay_hist).tolist()},
        "share": {"edges": _edges(0, 1, cfg.share_bins),
                  "counts": np.asarray(ts.share_hist).tolist()},
        "occupancy": {"edges": _edges(0, 1, cfg.occ_bins),
                      "counts": np.asarray(ts.occ_hist).tolist()}}
    series: dict = {}
    stacked = _stack_rows(rows)
    if stacked is not None:
        n_act = np.asarray(stacked.n_active, float)
        live = np.maximum(n_act, 1.0)
        series = {
            "occupancy": np.asarray(stacked.n_active).tolist(),
            "rmse_mbps": np.sqrt(
                np.asarray(stacked.err_sq_sum, float) / live).tolist(),
            "mean_delay_s": (np.asarray(stacked.delay_sum, float)
                             / live).tolist(),
            "admitted": np.asarray(stacked.admitted).tolist(),
            "departed": np.asarray(stacked.departed).tolist()}
    count = int(ts.events.count)
    kinds = np.asarray(ts.events.kind)[:count]
    evp = np.asarray(ts.events.period)[:count]
    args = np.asarray(ts.events.arg)[:count]
    vals = np.asarray(ts.events.val, float)[:count]
    order = np.argsort(evp, kind="stable")
    events = [TelemetryEvent(kind=EVENT_NAMES.get(int(kinds[i]),
                                                  str(int(kinds[i]))),
                             period=int(evp[i]), arg=int(args[i]),
                             value=float(vals[i])) for i in order]
    return TelemetryRecord(
        periods=periods, active_steps=active_steps,
        admitted=int(ts.admitted), departed=int(ts.departed),
        handovers=int(ts.handovers), stats=stats, hists=hists,
        series=series, events=events, dropped_events=int(ts.events.dropped))


# -------------------------------------------------------------- exporters
def to_jsonl(record: TelemetryRecord, path: str,
             period_s: float = 0.1) -> None:
    """JSON-lines time series: one object per report period (skipped when
    the record kept no per-period rows), then one ``summary`` line."""
    import json
    import os
    parent = os.path.dirname(path)
    if parent:
        os.makedirs(parent, exist_ok=True)
    names = list(record.series)
    with open(path, "w") as f:
        for t in range(len(record.series.get("occupancy", []))):
            row = {"period": t, "t_s": t * period_s}
            row.update({k: record.series[k][t] for k in names})
            f.write(json.dumps(row) + "\n")
        summary = record.to_dict()
        summary.pop("series", None)
        f.write(json.dumps({"summary": summary}) + "\n")


def to_prometheus(record: TelemetryRecord, prefix: str = "fleet") -> str:
    """The record as Prometheus text exposition (counters, stat gauges,
    cumulative ``_bucket`` histograms) — what a scrape endpoint serving
    one run's telemetry would return."""
    lines = []

    def counter(name, value, help_):
        lines.append(f"# HELP {prefix}_{name} {help_}")
        lines.append(f"# TYPE {prefix}_{name} counter")
        lines.append(f"{prefix}_{name} {value}")

    counter("periods_total", record.periods, "report periods observed")
    counter("active_slot_steps_total", record.active_steps,
            "live (slot, period) samples")
    counter("admitted_total", record.admitted, "sessions admitted")
    counter("departed_total", record.departed, "sessions departed")
    counter("handovers_total", record.handovers, "cell handovers")
    counter("events_dropped_total", record.dropped_events,
            "events that found the ring full")
    for name, st in record.stats.items():
        base = f"{prefix}_{name}"
        lines.append(f"# HELP {base} running {name} statistics")
        lines.append(f"# TYPE {base} gauge")
        for agg in ("mean", "min", "max"):
            lines.append(f'{base}{{agg="{agg}"}} {st[agg]}')
    for hname, h in record.hists.items():
        base = f"{prefix}_{hname}"
        lines.append(f"# HELP {base} {hname} histogram")
        lines.append(f"# TYPE {base} histogram")
        cum = 0
        for edge, c in zip(h["edges"][1:], h["counts"]):
            cum += c
            lines.append(f'{base}_bucket{{le="{edge:g}"}} {cum}')
        lines.append(f'{base}_bucket{{le="+Inf"}} {cum}')
        lines.append(f"{base}_count {cum}")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------- stage tracing
@contextlib.contextmanager
def stage(name: str, **stats):
    """A named report-period stage: ``jax.named_scope`` labels any ops
    traced inside it and ``jax.profiler.TraceAnnotation`` spans its host
    wall time on the profiler timeline, on the same clock as the device's
    ops. ``stats`` (ints, floats, strings) ride on the span as event
    stats; the span is yielded, so ``span.set_metadata(...)`` can add more
    before it closes. With no profiler session open it costs a few
    microseconds. Numerically a no-op."""
    with jax.named_scope(name), \
            jax.profiler.TraceAnnotation(name, **stats) as span:
        yield span


class _Put:
    """``jnp.asarray`` that counts the bytes of what it made."""

    def __init__(self):
        self.nbytes = 0

    def __call__(self, x, dtype=None):
        out = jnp.asarray(x, dtype)
        self.nbytes += out.nbytes
        return out


@contextlib.contextmanager
def ingest():
    """One stage's host-to-device copies as one ``ingest`` span. Yields
    ``put`` (``jnp.asarray`` on host arrays); when the span closes, its
    ``bytes`` stat is the ``nbytes`` of every array ``put`` made. Build
    the host array inside the span too, so the cast and reshape that feed
    a copy count as ingest."""
    put = _Put()
    with stage("ingest") as span:
        yield put
        span.set_metadata(bytes=put.nbytes)


@contextlib.contextmanager
def trace_capture(log_dir: Optional[str]):
    """Opt-in ``jax.profiler.trace`` capture: with a dir, the enclosed run
    lands as a TensorBoard-loadable profile; with None, a no-op."""
    if log_dir is None:
        yield
    else:
        with jax.profiler.trace(log_dir):
            yield


class StageStat(NamedTuple):
    """Wall-time summary of repeated stage runs, in seconds."""

    best: float
    median: float
    spread: float  # max - min over the reps

    def ms(self) -> dict:
        return {"best_ms": self.best * 1e3, "median_ms": self.median * 1e3,
                "spread_ms": self.spread * 1e3}


def timed(fn: Callable[[], object], reps: int = 2) -> StageStat:
    """Time ``fn()`` ``reps`` times (call once beforehand to warm jit
    caches): best filters scheduler noise, median is the honest center,
    spread flags unstable hosts."""
    times = []
    for _ in range(max(1, int(reps))):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return StageStat(best=min(times), median=float(np.median(times)),
                     spread=max(times) - min(times))

