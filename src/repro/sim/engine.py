"""Fleet-scale adaptive-splitting simulation engine.

Runs N UEs x S scenarios through the paper's full adaptive path —
channel -> KPM/IQ -> throughput estimate -> EWMA/hysteresis controller ->
PSO lookup -> split metrics — as one vectorized program:

  * episodes come in as an ``EpisodeBatch`` ((N, T, ...) arrays, see
    ``repro.channel.scenarios.gen_episode_batch``),
  * the whole fleet's throughput predictions come from a single estimator
    ``predict`` call per 0.1 s report period,
  * the N controllers advance as ``vmap(controller_step)`` inside one
    ``lax.scan`` over report periods,
  * delay/privacy/energy are gathered for the fleet in one indexing pass.

``simulate_fleet_looped`` is the legacy per-UE, per-step Python loop kept
as the equivalence reference and speedup baseline; both paths produce
bit-identical split decisions (they share ``controller_step``) and
float-identical metrics.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.channel import kpm as kpmmod
from repro.channel import throughput as tpmod
from repro.channel.scenarios import SCENARIOS, WINDOW, EpisodeBatch
from repro.core.controller import (AdaptiveSplitController, ControllerConfig,
                                   controller_init, controller_step)
from repro.core.energy import EDGE_A40X2, UE_VM_2CORE, DeviceProfile
from repro.core.objective import Constraints, Weights, evaluate
from repro.core.profiles import SplitProfile
# the estimator clamp range is part of the PSO sweep config, not ours
from repro.core.pso import TP_CLIP_MBPS, LookupTable, StackedLookupTable
from repro.estimator.serve import check_quant, fwd_int8, quantize_estimator
from repro.estimator.ssm import (SSMConfig, episode_features, iq_columns,
                                 reduce_forecasts, report_alloc,
                                 report_features, ssm_forward_seq)
from repro.estimator.train import fwd
from repro.kernels.featurize import kpm_feature_windows
from repro.sim import telemetry as telmod
from repro.sim.sched import SchedulerConfig, scheduler_init, scheduler_step
from repro.sim.serving import (ServingMesh, sharded_fleet_estimate,
                               sharded_ssm_estimate)
from repro.sim.telemetry import TelemetryConfig


@dataclasses.dataclass
class FleetResult:
    """Per-UE, per-report-period outcome of a fleet simulation."""

    splits: np.ndarray  # (N, T) int32 — deployed split per period
    true_tp: np.ndarray  # (N, T) Mbps ground truth
    est_tp: np.ndarray  # (N, T) Mbps fed to the controllers
    delay_s: np.ndarray  # (N, T) E2E delay at the deployed split
    privacy: np.ndarray  # (N, T) dCor leak at the deployed split
    energy_j: np.ndarray  # (N, T) UE energy at the deployed split
    fixed: Optional["FleetResult"] = None  # fixed-split baseline, same shapes
    prb_share: Optional[np.ndarray] = None  # (N, T) gNB PRB grant, if
    # a scheduler ran; None on the default (uncontended) path
    online: Optional[object] = None  # sim.online.OnlineStats when the run
    # adapted the estimator online; None on the default (frozen) path
    active: Optional[np.ndarray] = None  # (N, T) bool slot-occupancy mask
    # when the run churned (rows are pool slots, not fixed UEs); None on
    # the batch-synchronous path, where every (u, t) cell is live
    lifecycle: Optional[object] = None  # sim.pool.LifecycleStats when the
    # run churned (admissions, departures, admission latency); else None
    telemetry: Optional[object] = None  # sim.telemetry.TelemetryRecord when
    # the run was passed ``telemetry=TelemetryConfig(...)``; None (and
    # nothing of the metric plane traced) on the default path

    @property
    def n_ues(self) -> int:
        return self.splits.shape[0]

    @property
    def n_steps(self) -> int:
        return self.splits.shape[1]

    def scenario_means(self, scenario_idx: np.ndarray) -> dict:
        """Per-scenario (delay, privacy, energy) means, keyed by name."""
        out = {}
        for i in np.unique(np.asarray(scenario_idx)):
            rows = scenario_idx == i
            name = SCENARIOS[i] if 0 <= i < len(SCENARIOS) else str(i)
            out[name] = np.array([self.delay_s[rows].mean(),
                                  self.privacy[rows].mean(),
                                  self.energy_j[rows].mean()])
        return out


# Transmission-delay guard: an idle slot or a zero-PRB grant has no link,
# and dividing by its 0 bps would poison delay means with inf. Any real
# link is floored far above this (``throughput.max_throughput_mbps`` never
# drops below 0.5 Mbps and PRB scaling below ``PRB_FLOOR_MBPS`` = 0.01
# Mbps = 1e4 bps), so clamping at 1 bps is bit-invisible to live traffic.
TP_FLOOR_BPS = 1.0


def split_metrics(profile: SplitProfile, splits: np.ndarray,
                  tp_mbps: np.ndarray, ue: DeviceProfile = UE_VM_2CORE,
                  server: DeviceProfile = EDGE_A40X2
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(delay_s, privacy, energy_j) for a whole fleet in one gather.

    Element-for-element identical to ``evaluate(...)`` at the chosen split
    (same operations in the same order, float64 throughout). Throughput is
    floored at ``TP_FLOOR_BPS`` so zero/near-zero rates yield huge-but-
    finite delays instead of inf/NaN."""
    l = np.asarray(splits)
    tp_bps = np.maximum(np.asarray(tp_mbps, float) * 1e6, TP_FLOOR_BPS)
    delay = (profile.d_ue(ue)[l] + profile.d_ser(server)[l]
             + profile.data_bytes[l] * 8.0 / tp_bps)
    return delay, profile.privacy[l], profile.e_ue(ue)[l]


@functools.lru_cache(maxsize=None)
def _sweep_fn(ewma_alpha: float, hysteresis_steps: int, fallback_split: int,
              sched: Optional[SchedulerConfig] = None, n_cells: int = 1,
              telem: Optional[TelemetryConfig] = None):
    """The compiled controller scan, cached per controller, scheduler and
    telemetry config (jit's own cache then handles distinct fleet shapes).

    ``sweep(tab, warm, est, rate, cells, dconst, dbytes, ts0)`` returns
    ``(splits, shares, ts, rows)``. One scan body serves every config:
    which parts it holds is decided by Python branches at trace time, and
    the arguments and outputs a config does not use are None (no leaves),
    so ``sched=None, telem=None`` traces ``(tab, warm, est) -> splits``
    with nothing of the scheduler or the metric plane in it.

    Without a scheduler the controllers consume the estimates as-is. With
    one, the gNB scheduler runs *inside* the scan so allocation,
    estimation and splitting co-evolve: each period it divides every
    cell's PRB budget over its attached UEs (PF state carried across
    periods) from ``rate``, the full-grant rate, and each controller sees
    its estimate scaled by the share it was granted. With ``telem`` the
    carry also holds a ``TelemetryState`` that ``telemetry_step`` folds
    each period's splits, error, delay and shares into against ``rate``
    as the truth; on the scheduled arm the carry keeps the previous
    period's cell index and logs its changes as handover events."""
    cfg = ControllerConfig(ewma_alpha, hysteresis_steps, fallback_split)
    step = functools.partial(controller_step, cfg=cfg)

    def _t(x):
        return None if x is None else x.T

    @jax.jit
    def sweep(tab, warm, est, rate=None, cells=None, dconst=None,
              dbytes=None, ts0=None):
        n = tab.shape[0]
        init = (controller_init(warm, batch_shape=(n,)),
                None if sched is None else scheduler_init(n), ts0,
                None if sched is None or telem is None else cells[:, 0])
        periods = (None if telem is None
                   else jnp.arange(est.shape[1], dtype=jnp.int32))

        def body(carry, xs):
            ctl, ss, ts, prev_cell = carry
            est_t, rate_t, cell_t, t = xs
            share = None
            if sched is not None:
                with jax.named_scope("scheduler_step"):
                    ss, share = scheduler_step(sched, n_cells, ss, cell_t,
                                               rate_t)
            with jax.named_scope("controller_step"):
                ctl, split = jax.vmap(step)(
                    tab, ctl, est_t if share is None else est_t * share)
            row = None
            if telem is not None:
                with jax.named_scope("telemetry_step"):
                    if sched is None:
                        extra = {"share": jnp.ones((n,), jnp.float32)}
                    else:
                        # what split_metrics sees: PRB-scaled, floored
                        eff = jnp.maximum(rate_t * jnp.clip(share, 0.0, 1.0),
                                          tpmod.PRB_FLOOR_MBPS)
                        hand = (cell_t != prev_cell).sum(dtype=jnp.int32)
                        extra = {"share": share, "eff_tp": eff,
                                 "n_handover": hand}
                        prev_cell = cell_t
                    ts, row = telmod.telemetry_step(
                        telem, ts, period=t, split=split, est_tp=est_t,
                        true_tp=rate_t, active=jnp.ones((n,), bool),
                        dconst=dconst, dbytes=dbytes, **extra)
            return (ctl, ss, ts, prev_cell), (split, share, row)

        (_, _, ts, _), (splits, shares, rows) = lax.scan(
            body, init, (est.T, _t(rate), _t(cells), periods))
        return splits.T, _t(shares), ts, rows

    return sweep


def _run_sweep(tables, est_tp, cfg: ControllerConfig, warm_split,
               true_tp=None, *, sched: Optional[SchedulerConfig] = None,
               n_cells: int = 1, cell_idx=None,
               telem: Optional[TelemetryConfig] = None, dconst=None,
               dbytes=None, ts0=None):
    """One controller scan in the ``controller`` span: copy in what the
    config reads (``true_tp`` only with a scheduler or telemetry,
    ``cell_idx`` only with a scheduler), dispatch, read the splits and
    shares back. Returns ``(splits, shares, ts, rows)``; ``shares`` is
    None without a scheduler, ``ts`` and ``rows`` (left on the device)
    without telemetry."""
    sweep = _sweep_fn(cfg.ewma_alpha, cfg.hysteresis_steps,
                      cfg.fallback_split, sched, int(n_cells), telem)
    with telmod.stage("controller"):
        with telmod.ingest() as put:
            args = (put(tables, jnp.int32), put(warm_split, jnp.int32),
                    put(est_tp, jnp.float32),
                    None if sched is None and telem is None
                    else put(true_tp, jnp.float32),
                    None if sched is None else put(cell_idx, jnp.int32))
        splits, shares, ts, rows = sweep(*args, dconst, dbytes, ts0)
        return (np.asarray(splits),
                None if shares is None else np.asarray(shares), ts, rows)


def run_controllers(tables: np.ndarray, est_tp: np.ndarray,
                    cfg: ControllerConfig, warm_split) -> np.ndarray:
    """(N, T) splits: N controllers over T periods as one vmap+scan.

    ``tables``: (N, tp_max+1) stacked lookup rows (``StackedLookupTable
    .tables``); ``warm_split``: scalar or (N,) deployed-split warm start."""
    return _run_sweep(tables, est_tp, cfg, warm_split)[0]


def run_scheduled(tables: np.ndarray, est_tp: np.ndarray,
                  cfg: ControllerConfig, warm_split,
                  sched: SchedulerConfig, n_cells: int, cell_idx: np.ndarray,
                  rate_mbps: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """((N, T) int32 splits, (N, T) float PRB shares in [0, 1]): scheduler
    + controllers in one scan.

    ``tables``: (N, tp_max+1) stacked lookup rows; ``est_tp``: (N, T)
    estimated full-grant throughput in Mbps (each controller consumes
    ``est_tp * share``); ``cell_idx``: (N, T) cell of each UE per period
    (inter-cell handover = the index changing mid-episode);
    ``rate_mbps``: (N, T) the gNB's CQI view (full-grant achievable rate,
    Mbps) driving the scheduler. This is the ``sched is not None`` arm of
    ``simulate_fleet``."""
    return _run_sweep(tables, est_tp, cfg, warm_split, rate_mbps,
                      sched=sched, n_cells=n_cells, cell_idx=cell_idx)[:2]


def emit_period_samples(episode: EpisodeBatch, t: int,
                        wins: Optional[np.ndarray] = None, *,
                        trace: Optional[np.ndarray] = None) -> dict:
    """The (kpms, iq, alloc -> measured tp) sample batch report period
    ``t`` emits: N rows of estimator inputs plus the period's *measured*
    throughput in Mbps — the label the fleet observes for free after
    acting, which is what the online replay buffer (``repro.sim.online``)
    ingests and what ``estimate_fleet`` feeds the estimator (``predict``
    only reads ``tp`` for its length).

    ``wins``: optionally the precomputed float32
    ``episode.kpm_windows(normalize=True)`` so per-period callers amortize
    the window view across the episode. ``trace``: the fused-featurize
    alternative — the (N, T + WINDOW, 15) *normalized* float32 KPM trace;
    period ``t``'s window is then the ``trace[:, t:t+WINDOW]`` view, the
    same f32 elements as ``wins[:, t]`` without ever materializing the
    (N, T, WINDOW, 15) tensor."""
    if trace is not None:
        kp = trace[:, t:t + WINDOW]
    else:
        if wins is None:
            wins = episode.kpm_windows(normalize=True).astype(np.float32)
        kp = wins[:, t]
    return {"kpms": kp,
            "iq": episode.iq[:, t].astype(np.float32),
            "alloc": episode.alloc_ratio.astype(np.float32),
            "tp": episode.tp_mbps[:, t].astype(np.float32)}


# Device bytes one forward of the windowed estimator may hold on the
# unsharded path. Rows per forward follow from it (``est_chunk_rows``), so a
# chunk fits one chip's HBM at any IQ width while amortizing dispatch over
# many report periods per call.
EST_CHUNK_BYTES = 1 << 30
# UEs per recurrent-estimator sequence pass (one row = one UE's stream)
SSM_CHUNK_UES = 8192


def est_chunk_rows(ecfg) -> int:
    """Rows per estimator forward: as many ``ecfg.row_bytes`` rows as fit
    ``EST_CHUNK_BYTES`` (about 225 at the paper's n_sc=3276, 11k at
    n_sc=64)."""
    return max(1, EST_CHUNK_BYTES // ecfg.row_bytes)


def estimate_fleet(episode: EpisodeBatch, estimator, tp_clip=TP_CLIP_MBPS,
                   *, serving: Optional[ServingMesh] = None,
                   quant: Optional[str] = None,
                   fused: bool = False) -> np.ndarray:
    """(N, T) estimated throughput in Mbps, clipped into ``tp_clip``.

    Batched inference over the fleet (the AF's batch path): period ``t``
    sees each UE's (WINDOW, 15) KPM window ending just before ``t`` plus
    its (2, n_sc, 14) IQ spectrogram, and the fused prediction is clipped
    into the PSO sweep range (Mbps, default ``TP_CLIP_MBPS``). The
    unsharded path vectorizes *across report periods* too: as many whole
    periods of a block of UEs as fit in ``est_chunk_rows(ecfg)`` rows are
    flattened into one jitted forward (periods x UE rows), so a T-period
    episode costs about ``N * T / est_chunk_rows`` dispatches instead of
    T — the numbers are identical to the old per-period loop because the
    forward is row-wise (pinned by ``tests/test_sim_fleet.py``).

    ``estimator``: an ``(EstimatorConfig, params)`` pair, or an
    ``(SSMConfig, params)`` pair — the recurrent estimator
    (``repro.estimator.ssm``), which consumes the raw KPM report stream
    (no IQ, no windows) through one chunked SSD sequence pass and emits
    policy-reduced forecast estimates; ``fused`` is then a no-op (there
    is no window featurize to fuse) and ``quant`` must be None (the
    recurrent path serves fp32). ``serving``: an
    optional ``repro.sim.serving.ServingMesh``; when given, each period's
    forward runs as the mesh-sharded SPMD program — UE batch sharded over
    the mesh's data axis, weights replicated — instead of the
    single-device ``predict`` path. Both paths compute the same per-UE
    math; they are pinned allclose by ``tests/test_serving_mesh.py``.

    ``fused=True`` replaces the host stride-trick window materialization
    — a WINDOW x blowup of the whole KPM trace — with the fused featurize
    path: per chunk, the ``kernels/featurize`` Pallas kernel normalizes
    and windows the raw trace on device (under a serving mesh, the
    equivalent per-period trace *view*, which is bit-identical to the
    unfused elements). ``quant="int8"`` serves ``quantize_estimator``
    weights through the int8 kernels (``estimator.serve``). Both default
    off; ``fused=False, quant=None`` is the exact prior program (pinned
    by ``tests/test_sim_fused.py``).
    """
    ecfg, params = estimator
    if isinstance(ecfg, SSMConfig):
        return _estimate_fleet_ssm(episode, ecfg, params, tp_clip,
                                   serving=serving, quant=quant)
    check_quant(quant)
    if fused and episode.kpms is None:
        raise ValueError("fused featurize needs raw KPM reports: generate "
                         "the episode with include_kpms=True")
    if episode.iq is None:
        raise ValueError(
            "estimator inference needs IQ spectrograms: generate the episode "
            "with include_iq=True")
    n, t_steps = episode.n_ues, episode.n_steps
    alloc = episode.alloc_ratio.astype(np.float32)
    if serving is not None:
        with telmod.stage("featurize"):
            if fused:
                # normalized trace, windowed per period as a view (the f64
                # normalize + f32 cast matches kpm_windows bit-for-bit)
                wins = kpmmod.normalize_kpms(episode.kpms).astype(np.float32)
            else:
                wins = episode.kpm_windows(normalize=True).astype(np.float32)
        return sharded_fleet_estimate(ecfg, params, wins, episode.iq, alloc,
                                      serving, tp_clip, quant=quant,
                                      window=WINDOW if fused else None)
    qparams = quantize_estimator(params) if quant == "int8" else None
    if fused:
        with telmod.stage("featurize"), telmod.ingest() as put:
            kpms_d = put(episode.kpms, jnp.float32)
            center = put(kpmmod.KPM_CENTER)
            scale = put(kpmmod.KPM_SCALE)
    else:
        with telmod.stage("featurize"):
            wins = episode.kpm_windows(normalize=True).astype(np.float32)
    est = np.empty((n, t_steps))
    max_rows = est_chunk_rows(ecfg)
    n_blk = max(1, min(n, max_rows))  # UEs per chunk: < n only at wide IQ
    periods = max(1, min(t_steps, max_rows // n_blk))
    for u0, t0 in itertools.product(range(0, n, n_blk),
                                    range(0, t_steps, periods)):
        us = slice(u0, min(u0 + n_blk, n))
        m = us.stop - u0
        b = min(periods, t_steps - t0)
        sl = slice(t0, t0 + b)
        rows = m * b
        # (m, b, ...) -> (m*b, ...): row (u * b + j) is UE u0+u at period
        # t0+j
        if fused:
            with telmod.stage("featurize"):
                # window j of the chunk covers trace steps
                # [t0+j, t0+j+WINDOW)
                kw = kpm_feature_windows(
                    kpms_d[us, t0:t0 + b + WINDOW - 1], center, scale,
                    WINDOW)
                kpms_rows = kw.reshape(rows, WINDOW, kw.shape[-1])
        with telmod.stage("estimator_fwd"):
            with telmod.ingest() as put:
                if not fused:
                    kpms_rows = put(np.ascontiguousarray(
                        wins[us, sl]).reshape(rows, *wins.shape[2:]))
                iq_rows = put(np.asarray(episode.iq[us, sl],
                                         np.float32).reshape(
                    rows, *episode.iq.shape[2:]))
                alloc_rows = put(np.repeat(alloc[us], b))
            if quant == "int8":
                out = fwd_int8(ecfg, qparams, kpms_rows, iq_rows, alloc_rows)
            else:
                out = fwd(ecfg, params, kpms_rows, iq_rows, alloc_rows)
            est[us, sl] = np.asarray(out).reshape(m, b)
    return np.clip(est, tp_clip[0], tp_clip[1])


def _estimate_fleet_ssm(episode: EpisodeBatch, ecfg: SSMConfig, params,
                        tp_clip, *, serving: Optional[ServingMesh] = None,
                        quant: Optional[str] = None) -> np.ndarray:
    """The recurrent arm of :func:`estimate_fleet`: the whole (N, T +
    WINDOW) report stream runs through one chunked SSD sequence pass per
    ``SSM_CHUNK_UES`` UEs (the first WINDOW - 1 reports warm the state,
    matching the windowed path's label alignment), and the (K+1)
    forecasts collapse to the policy estimate. Each chunk's raw reports
    are copied in and made into features on the device
    (``report_features``). Under a ``serving`` mesh
    the same math runs as the per-period O(1) step program, state
    sharded over the batch axis (pinned allclose by
    ``tests/test_estimator_ssm.py``)."""
    if quant is not None:
        raise ValueError("int8 serving applies to the windowed estimator; "
                         "the recurrent estimator serves fp32 weights")
    if episode.kpms is None:
        raise ValueError("the recurrent estimator needs raw KPM reports: "
                         "generate the episode with include_kpms=True")
    if ecfg.include_iq and episode.iq is None:
        raise ValueError("SSMConfig(include_iq=True) needs spectrogram "
                         "snapshots: generate the episode with "
                         "include_iq=True")
    n, t_steps = episode.n_ues, episode.n_steps
    iq = episode.iq if ecfg.include_iq else None
    if serving is not None:
        with telmod.stage("featurize"):
            feats = episode_features(episode.kpms, episode.alloc_ratio, iq)
        return sharded_ssm_estimate(ecfg, params, feats, serving, tp_clip,
                                    n_periods=t_steps)
    s = episode.kpms.shape[1]
    if iq is not None:
        with telmod.stage("featurize"):
            iq = iq_columns(iq, s)
    est = np.empty((n, t_steps))
    for i in range(0, n, SSM_CHUNK_UES):
        rows = slice(i, min(i + SSM_CHUNK_UES, n))
        # the chunk's raw reports go in flat; its features are built on
        # the device and freed with its pass, not held over the next copy
        with telmod.stage("featurize"):
            with telmod.ingest() as put:
                kpms_d = put(episode.kpms[rows].reshape(-1), jnp.float32)
                alloc_d = put(report_alloc(episode.alloc_ratio[rows], s))
                iq_d = None if iq is None else put(iq[rows])
            x = report_features((rows.stop - i, s), kpms_d, alloc_d,
                                kpmmod.KPM_CENTER, kpmmod.KPM_SCALE, iq_d)
            del kpms_d, alloc_d, iq_d
        with telmod.stage("estimator_fwd"):
            fc, _ = ssm_forward_seq(ecfg, params, x)
            del x
            est[rows] = reduce_forecasts(
                ecfg, np.asarray(fc[:, WINDOW - 1:WINDOW - 1 + t_steps]))
    return np.clip(est, tp_clip[0], tp_clip[1])


def _host_telemetry(telemetry: Optional[TelemetryConfig],
                    profile: SplitProfile, ue: DeviceProfile,
                    server: DeviceProfile):
    """(``HostTelemetry``, per-split delay constants, boundary bytes) for
    a run with ``telemetry``; three Nones without it."""
    if telemetry is None:
        return None, None, None
    dconst = np.asarray(profile.d_ue(ue)) + np.asarray(profile.d_ser(server))
    return (telmod.HostTelemetry(telemetry), jnp.asarray(dconst, jnp.float32),
            jnp.asarray(profile.data_bytes, jnp.float32))


def simulate_fleet(episode: EpisodeBatch, table, profile: SplitProfile,
                   cfg: ControllerConfig, *, warm_split=None, estimator=None,
                   serving: Optional[ServingMesh] = None,
                   online=None,
                   fixed_split: Optional[int] = None,
                   ue: DeviceProfile = UE_VM_2CORE,
                   server: DeviceProfile = EDGE_A40X2,
                   sched: Optional[SchedulerConfig] = None,
                   cell_idx: Optional[np.ndarray] = None,
                   n_cells: int = 1,
                   churn=None, capacity: Optional[int] = None,
                   quant: Optional[str] = None,
                   fused: bool = False,
                   telemetry: Optional[TelemetryConfig] = None
                   ) -> FleetResult:
    """Vectorized fleet simulation (the production path).

    Consumes an ``EpisodeBatch`` of N UEs over T report periods (0.1 s
    each) and returns a ``FleetResult`` of (N, T) arrays: int32 split
    decisions, throughputs in Mbps, E2E delay in seconds, dCor privacy
    leakage in [0, 1], and per-inference UE energy in joules.

    ``table``: one ``LookupTable`` shared by the fleet or a
    ``StackedLookupTable`` with one row per UE. ``warm_split`` defaults to
    ``fixed_split`` (the AF streams reports before this window) or NO_SPLIT.
    ``estimator``: optional (EstimatorConfig, params); without it the
    controllers see the ground-truth throughput. ``serving``: optional
    ``repro.sim.serving.ServingMesh`` forwarded to ``estimate_fleet`` so
    the per-period estimator inference runs mesh-sharded (ignored without
    an ``estimator``). ``fixed_split`` also attaches the fixed-policy
    baseline metrics as ``result.fixed``.

    ``online`` (default None): a ``repro.sim.online.OnlineConfig`` closes
    the estimate->act->observe->learn loop — the per-report-period
    estimator forward runs with *continually adapted* weights: each
    period's measured throughput is ring-ingested as a free training
    label, a drift monitor watches the estimator RMSE, and when it trips
    the online trainer runs K jitted AdamW steps on the replay buffer
    (under the serving mesh when one is given) before the next period's
    predict. The resulting ``FleetResult.online`` carries the adaptation
    trace (per-period RMSE, bursts, checkpoints). Requires ``estimator``.

    ``sched`` (default None): a ``SchedulerConfig`` puts a gNB PRB
    scheduler inside the scan. ``cell_idx`` (N, T) assigns each UE to one
    of ``n_cells`` cells per period; every UE's throughput — the estimate
    its controller consumes and the ground truth its metrics are gathered
    at — is scaled by the PRB share the scheduler granted it (see
    ``repro.sim.cells`` for the orchestration layer).

    ``churn`` (default None): a ``repro.channel.scenarios.ChurnSchedule``
    switches the engine to the slot-pool path (``repro.sim.pool``): the
    episode's N rows become *sessions* that arrive, live for their dwell,
    and depart, served from a fixed ``capacity``-slot device-resident
    pool. Rows of the result are then pool slots over time, with
    ``result.active`` marking occupancy and ``result.lifecycle`` carrying
    admission/departure stats; ``cell_idx`` is interpreted as a (N,)
    per-session static cell attach.

    Equivalence guarantee: with ``sched=None`` the scheduler branch of
    the controller scan is not traced, so the controllers consume the
    estimates as-is (pinned by ``tests/test_sim_cells.py`` and the
    ``cells/noop_equivalence`` benchmark record). Sharded serving does not
    weaken this: it changes where the estimator forward runs, not the
    controller scan. Likewise ``online=None`` (the default) never touches
    ``repro.sim.online`` — the estimates are the frozen estimator's
    (pinned by ``tests/test_sim_online.py``) — and ``churn=None`` (the
    default) never touches ``repro.sim.pool``: the batch-synchronous path
    below runs (the degenerate full pool is pinned equal to it by
    ``tests/test_sim_pool.py``).

    ``quant`` / ``fused`` (defaults None / False): the int8 serving and
    fused-featurize switches, forwarded to ``estimate_fleet`` (and the
    pool/online loops). They change how the per-period estimates are
    computed, never the controller scan; the defaults spelled out run
    the default program, and the fused path's estimates match it to
    float tolerance with the same splits (both pinned by
    ``tests/test_sim_fused.py``). ``quant`` requires a frozen estimator
    (the online trainer adapts fp32 weights).

    ``telemetry`` (default None): a ``repro.sim.telemetry.TelemetryConfig``
    turns on the in-scan metric plane — counters, running stats,
    fixed-bucket histograms and the typed event ring accumulate on device
    inside the controller scan (and the online loop logs drift/burst/swap
    events into the same ring), decoded once at run end into
    ``FleetResult.telemetry`` (a ``TelemetryRecord``). ``telemetry=None``
    never builds any of it (the scan's telemetry branch is not traced),
    and telemetry on leaves the splits and metrics bit-identical (pinned
    by ``tests/test_sim_telemetry.py``). ``TelemetryConfig(trace_dir=...)``
    additionally wraps the run in a ``jax.profiler.trace`` capture.
    """
    check_quant(quant)
    if online is not None and quant is not None:
        raise ValueError(
            "online adaptation serves the fp32 weights it trains; int8 "
            "serving (quant=...) needs a frozen estimator")
    if churn is not None:
        from repro.sim.pool import simulate_pool
        if capacity is None:
            raise TypeError("churn=... needs an explicit capacity=N_slots")
        return simulate_pool(episode, churn, table, profile, cfg,
                             capacity=capacity, warm_split=warm_split,
                             estimator=estimator, serving=serving,
                             online=online, fixed_split=fixed_split,
                             ue=ue, server=server, sched=sched,
                             cell=cell_idx, n_cells=n_cells,
                             quant=quant, fused=fused, telemetry=telemetry)
    tables = (table.tables if isinstance(table, StackedLookupTable)
              else np.broadcast_to(table.table,
                                   (episode.n_ues, len(table.table))))
    true_tp = np.asarray(episode.tp_mbps, float)
    tel, dconst, dbytes = _host_telemetry(telemetry, profile, ue, server)
    online_stats = None
    with telmod.trace_capture(telemetry.trace_dir
                              if telemetry is not None else None):
        if online is not None:
            from repro.sim.online import online_estimate_fleet
            if estimator is None:
                raise ValueError("online adaptation needs an estimator")
            est_tp, online_stats = online_estimate_fleet(
                episode, estimator, online, serving=serving, fused=fused,
                telemetry=tel)
        else:
            est_tp = (estimate_fleet(episode, estimator, serving=serving,
                                     quant=quant, fused=fused)
                      if estimator is not None else true_tp)
        if warm_split is None:
            warm_split = (cfg.fallback_split if fixed_split is None
                          else fixed_split)
        if sched is not None and cell_idx is None:
            raise ValueError("a scheduler needs a (N, T) cell_idx")
        splits, shares, ts, rows = _run_sweep(
            tables, est_tp, cfg, warm_split, true_tp, sched=sched,
            n_cells=n_cells, cell_idx=cell_idx, telem=telemetry,
            dconst=dconst, dbytes=dbytes,
            ts0=tel.ts if tel is not None else None)
        eff_tp = true_tp
        if tel is not None:
            tel.ts = ts
        if shares is not None:
            eff_tp = tpmod.prb_scaled_mbps(true_tp, shares)
            est_tp = est_tp * shares  # what the controllers consumed
    with telmod.stage("split_metrics"):
        delay, priv, energy = split_metrics(profile, splits, eff_tp, ue,
                                            server)
        fixed = None
        if fixed_split is not None:
            fsplits = np.full_like(splits, fixed_split)
            fd, fp, fe = split_metrics(profile, fsplits, eff_tp, ue, server)
            fixed = FleetResult(fsplits, true_tp, est_tp, fd, fp, fe,
                                prb_share=shares)
    return FleetResult(splits, true_tp, est_tp, delay, priv, energy, fixed,
                       prb_share=shares, online=online_stats,
                       telemetry=tel.decode(rows) if tel is not None
                       else None)


def simulate_fleet_looped(episode: EpisodeBatch, table,
                          profile: SplitProfile, cfg: ControllerConfig, *,
                          warm_split=None, est_tp: Optional[np.ndarray] = None,
                          fixed_split: Optional[int] = None,
                          ue: DeviceProfile = UE_VM_2CORE,
                          server: DeviceProfile = EDGE_A40X2) -> FleetResult:
    """The legacy per-UE, per-report-period Python loop (pre-fleet fig6
    path): one ``AdaptiveSplitController`` per UE, one objective
    ``evaluate`` per UE per period. Kept as the equivalence reference and
    the speedup baseline for ``benchmarks/fleet.py``."""
    n, t_steps = episode.n_ues, episode.n_steps
    true_tp = np.asarray(episode.tp_mbps, float)
    if est_tp is None:
        est_tp = true_tp
    if warm_split is None:
        warm_split = cfg.fallback_split if fixed_split is None else fixed_split
    warm = np.broadcast_to(np.asarray(warm_split), (n,))
    splits = np.empty((n, t_steps), np.int32)
    acc = np.empty((n, t_steps, 3))
    facc = np.empty((n, t_steps, 3)) if fixed_split is not None else None
    for u in range(n):
        row = table.row(u) if isinstance(table, StackedLookupTable) else table
        ctl = AdaptiveSplitController(row, cfg)
        ctl.reset(warm_split=int(warm[u]))
        for t in range(t_steps):
            l = ctl.update(float(est_tp[u, t]))
            splits[u, t] = l
            terms = evaluate(profile, ue, server,
                             np.array([true_tp[u, t] * 1e6]),
                             Weights(1, 0, 0), Constraints())
            acc[u, t] = (terms.d_e2e[l, 0], profile.privacy[l], terms.e_ue[l])
            if facc is not None:
                facc[u, t] = (terms.d_e2e[fixed_split, 0],
                              profile.privacy[fixed_split],
                              terms.e_ue[fixed_split])
    fixed = None
    if facc is not None:
        fixed = FleetResult(np.full_like(splits, fixed_split), true_tp,
                            est_tp, facc[..., 0], facc[..., 1], facc[..., 2])
    return FleetResult(splits, true_tp, est_tp, acc[..., 0], acc[..., 1],
                       acc[..., 2], fixed)
