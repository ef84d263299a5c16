"""Closed-loop online estimator adaptation: drift-triggered continual
learning inside the fleet engine.

The paper's estimator is trained once offline and served frozen; under
the scenario/handover drift the fleet engine simulates, its error grows
and split decisions degrade. This module closes the missing half of the
serving loop — estimate -> act -> observe -> learn — at fleet scale,
using labels the fleet already produces for free (the measured per-period
throughput each report period emits):

  * :class:`ReplayBuffer` — a device-resident, fixed-capacity pure-jnp
    ring buffer of (kpms, iq, alloc -> measured tp) samples, row axis
    carrying the logical ``batch`` axis so under a ``ServingMesh`` the
    buffer itself is sharded over the mesh's data axis;
  * :func:`drift_step` — an EWMA monitor of the per-period estimator RMSE
    with a trigger threshold calibrated on the first healthy periods,
    plus patience (consecutive above-threshold periods to fire) and
    cooldown hysteresis so transient noise never triggers retraining;
  * :func:`online_estimate_fleet` — the per-report-period loop: predict
    with the current weights (the same cached ``sim.serving`` program an
    AF pod runs), ingest the period's samples, update the monitor, and on
    a trigger run K jitted AdamW steps on buffer minibatches — the step
    comes from ``estimator.train.make_indexed_step``, shared with the
    offline loop, traced under the serving mesh (data-sharded batch,
    replicated params, psum'd grads) — then swap the refreshed weights
    back into the serving cache (``serving.replicate_params``: a cache
    hit, no retrace) and checkpoint them via
    ``checkpoint.CheckpointManager``.

The four online loops — batch or slot pool, windowed or recurrent —
share that machinery through :class:`OnlineAdapter`.
``simulate_fleet(online=None)`` never enters this module: the engine
serves the frozen estimator (pinned by ``tests/test_sim_online.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.channel import kpm as kpmmod
from repro.checkpoint import CheckpointManager
from repro.core.pso import TP_CLIP_MBPS
from repro.dist import sharding as sh
from repro.estimator.model import EstimatorConfig
from repro.estimator.ssm import (SSMConfig, episode_features,
                                 reduce_forecasts, ssm_state_init, ssm_step)
from repro.estimator.train import (fwd, make_indexed_step,
                                   make_indexed_step_ssm)
from repro.kernels.quant.ref import quantize_ref
from repro.optim import AdamW
from repro.sim import telemetry as telmod
from repro.sim.serving import (STATE_AXES, ServingMesh, replicate_params,
                               serving_program, ssm_serving_program)

F32 = jnp.float32
I32 = jnp.int32

RING_QUANT_MODES = (None, "int8")


# --------------------------------------------------------------- buffer
class ReplayBuffer(NamedTuple):
    """Fixed-capacity ring of fleet samples, all leaves device-resident.

    Row 0..capacity-1 is the ring; ``head`` is the next write slot and
    ``seen`` the total rows ever ingested (``min(seen, capacity)`` rows
    are valid). The row axis is the logical ``batch`` axis: under a
    serving mesh the buffer shards over the data axis like any fleet
    batch."""

    kpms: jax.Array  # (C, WINDOW, 15) normalized KPM windows
    iq: jax.Array  # (C, 2, n_sc, 14) spectrograms
    alloc: jax.Array  # (C,) PRB allocation ratios
    tp: jax.Array  # (C,) measured throughput labels (Mbps)
    head: jax.Array  # i32 scalar — next write slot
    seen: jax.Array  # i32 scalar — total rows ever ingested

    @property
    def capacity(self) -> int:
        return self.tp.shape[0]


class ReplayBufferQ(NamedTuple):
    """The int8 ring (``OnlineConfig.ring_quant="int8"``): same contract
    as :class:`ReplayBuffer` but the two big sample tensors are stored as
    rowwise-quantized int8 plus one f32 scale per sample — the
    ``kernels/quant`` formula applied inside the ingest scatter, ~4x less
    replay memory. Minibatches are dequantized on the trainer's
    in-program gather (``estimator.train.make_indexed_step``)."""

    kpms_q: jax.Array  # (C, WINDOW, 15) int8
    kpms_s: jax.Array  # (C, 1) f32 rowwise scales
    iq_q: jax.Array  # (C, 2, n_sc, 14) int8
    iq_s: jax.Array  # (C, 1) f32 rowwise scales
    alloc: jax.Array  # (C,) PRB allocation ratios
    tp: jax.Array  # (C,) measured throughput labels (Mbps)
    head: jax.Array  # i32 scalar — next write slot
    seen: jax.Array  # i32 scalar — total rows ever ingested

    @property
    def capacity(self) -> int:
        return self.tp.shape[0]


class ReplayBufferSSM(NamedTuple):
    """The recurrent estimator's ring: each row is one report event —
    the per-UE SSD state *as it was* before the report, the report's
    features, and the measured-throughput label. Replaying a row re-runs
    exactly one recurrence step from the stored state (truncated BPTT,
    length 1 — ``estimator.train.ssm_step_loss``), so replay cost never
    depends on how much history the live states have absorbed. No int8
    variant: quantizing stored states would perturb every replayed
    gradient (``ring_quant`` is refused for SSM configs)."""

    state: jax.Array  # (C, G, nh//G, hd, N) pre-report recurrent states
    feats: jax.Array  # (C, F) report features
    tp: jax.Array  # (C,) measured throughput labels (Mbps)
    head: jax.Array  # i32 scalar — next write slot
    seen: jax.Array  # i32 scalar — total rows ever ingested

    @property
    def capacity(self) -> int:
        return self.tp.shape[0]


def _rowq(x):
    """Per-sample quantization of an (n, ...) batch: the ``kernels/quant``
    rowwise formula over each sample's flattened features."""
    q, s = quantize_ref(x.reshape(x.shape[0], -1))
    return q.reshape(x.shape), s


def buffer_init(capacity: int, e: EstimatorConfig,
                serving: Optional[ServingMesh] = None,
                quant: Optional[str] = None):
    """An empty ring for ``capacity`` rows of this estimator's shapes.

    With ``serving`` the sample arrays are committed row-sharded over the
    mesh's data axis (``dist.sharding.put`` under the ``batch`` rule); on
    a single device / no mesh they are plain device arrays.
    ``quant="int8"`` builds the quantized ring (:class:`ReplayBufferQ`).
    An :class:`~repro.estimator.ssm.SSMConfig` builds the recurrent ring
    (:class:`ReplayBufferSSM`; ``quant`` must then be None)."""
    if quant not in RING_QUANT_MODES:
        raise ValueError(
            f"ring_quant must be one of {RING_QUANT_MODES}: {quant!r}")
    if isinstance(e, SSMConfig):
        if quant is not None:
            raise ValueError(
                "ring_quant applies to the windowed estimator's ring; the "
                "recurrent ring stores states exactly (fp32)")
        z = {"state": jnp.zeros((capacity,) + e.state_shape(), F32),
             "feats": jnp.zeros((capacity, e.n_feats), F32),
             "tp": jnp.zeros((capacity,), F32)}
        cls = ReplayBufferSSM
    elif quant == "int8":
        z = {"kpms_q": jnp.zeros((capacity, e.window, e.n_kpms), jnp.int8),
             "kpms_s": jnp.ones((capacity, 1), F32),
             "iq_q": jnp.zeros((capacity, 2, e.n_sc, e.n_sym), jnp.int8),
             "iq_s": jnp.ones((capacity, 1), F32),
             "alloc": jnp.zeros((capacity,), F32),
             "tp": jnp.zeros((capacity,), F32)}
        cls = ReplayBufferQ
    else:
        z = {"kpms": jnp.zeros((capacity, e.window, e.n_kpms), F32),
             "iq": jnp.zeros((capacity, 2, e.n_sc, e.n_sym), F32),
             "alloc": jnp.zeros((capacity,), F32),
             "tp": jnp.zeros((capacity,), F32)}
        cls = ReplayBuffer
    if serving is not None:
        with sh.use_rules(serving.mesh, serving.rule_overrides()):
            z = {k: sh.put(v, ("batch",) + (None,) * (v.ndim - 1))
                 for k, v in z.items()}
    return cls(head=jnp.zeros((), I32), seen=jnp.zeros((), I32), **z)


@functools.partial(jax.jit, donate_argnums=0)
def _ring_scatter(buf: ReplayBuffer, kpms, iq, alloc, tp) -> ReplayBuffer:
    # the buffer is donated: callers always rebind (buf = buffer_add(buf,
    # ...)), so the .at[].set updates run in place instead of copying the
    # whole capacity-sized ring every report period
    cap = buf.tp.shape[0]
    n = tp.shape[0]
    idx = (buf.head + jnp.arange(n, dtype=I32)) % cap
    return ReplayBuffer(
        kpms=buf.kpms.at[idx].set(kpms),
        iq=buf.iq.at[idx].set(iq),
        alloc=buf.alloc.at[idx].set(alloc),
        tp=buf.tp.at[idx].set(tp),
        head=(buf.head + n) % cap,
        seen=buf.seen + n)


@functools.partial(jax.jit, donate_argnums=0)
def _ring_scatter_q(buf: ReplayBufferQ, kpms, iq, alloc,
                    tp) -> ReplayBufferQ:
    # same in-place ring write as _ring_scatter, with the two big tensors
    # rowwise-quantized inside the donated program (no fp32 staging copy)
    cap = buf.tp.shape[0]
    n = tp.shape[0]
    idx = (buf.head + jnp.arange(n, dtype=I32)) % cap
    kq, ks = _rowq(kpms)
    iqq, iqs = _rowq(iq)
    return ReplayBufferQ(
        kpms_q=buf.kpms_q.at[idx].set(kq),
        kpms_s=buf.kpms_s.at[idx].set(ks),
        iq_q=buf.iq_q.at[idx].set(iqq),
        iq_s=buf.iq_s.at[idx].set(iqs),
        alloc=buf.alloc.at[idx].set(alloc),
        tp=buf.tp.at[idx].set(tp),
        head=(buf.head + n) % cap,
        seen=buf.seen + n)


def buffer_add(buf, kpms, iq, alloc, tp):
    """Ring-ingest a batch of N sample rows (oldest rows overwritten).

    N > capacity keeps the newest ``capacity`` rows — the overflow is
    dropped *before* the scatter so its indices stay unique (a scatter
    with duplicate indices has no defined write order)."""
    cap = int(buf.tp.shape[0])
    n = int(np.shape(tp)[0])
    if n > cap:
        kpms, iq, alloc, tp = (x[-cap:] for x in (kpms, iq, alloc, tp))
    scatter = (_ring_scatter_q if isinstance(buf, ReplayBufferQ)
               else _ring_scatter)
    return scatter(buf, jnp.asarray(kpms, F32), jnp.asarray(iq, F32),
                   jnp.asarray(alloc, F32), jnp.asarray(tp, F32))


@functools.partial(jax.jit, donate_argnums=0)
def _ring_scatter_masked(buf: ReplayBuffer, kpms, iq, alloc, tp,
                         mask) -> ReplayBuffer:
    # masked rows are packed to the front of the write (cumsum of the mask
    # gives each valid row its offset from head) and the rest scattered to
    # index ``cap`` which ``mode="drop"`` discards — fixed shapes, so the
    # program never retraces as the live population churns
    cap = buf.tp.shape[0]
    m = mask.astype(I32)
    k = m.sum()
    pos = jnp.cumsum(m) - 1
    idx = jnp.where(mask, (buf.head + pos) % cap, cap)
    return ReplayBuffer(
        kpms=buf.kpms.at[idx].set(kpms, mode="drop"),
        iq=buf.iq.at[idx].set(iq, mode="drop"),
        alloc=buf.alloc.at[idx].set(alloc, mode="drop"),
        tp=buf.tp.at[idx].set(tp, mode="drop"),
        head=(buf.head + k) % cap,
        seen=buf.seen + k)


@functools.partial(jax.jit, donate_argnums=0)
def _ring_scatter_masked_q(buf: ReplayBufferQ, kpms, iq, alloc, tp,
                           mask) -> ReplayBufferQ:
    # _ring_scatter_masked with in-program rowwise quantization; masked
    # rows are quantized too (fixed shapes) but dropped at the scatter
    cap = buf.tp.shape[0]
    m = mask.astype(I32)
    k = m.sum()
    pos = jnp.cumsum(m) - 1
    idx = jnp.where(mask, (buf.head + pos) % cap, cap)
    kq, ks = _rowq(kpms)
    iqq, iqs = _rowq(iq)
    return ReplayBufferQ(
        kpms_q=buf.kpms_q.at[idx].set(kq, mode="drop"),
        kpms_s=buf.kpms_s.at[idx].set(ks, mode="drop"),
        iq_q=buf.iq_q.at[idx].set(iqq, mode="drop"),
        iq_s=buf.iq_s.at[idx].set(iqs, mode="drop"),
        alloc=buf.alloc.at[idx].set(alloc, mode="drop"),
        tp=buf.tp.at[idx].set(tp, mode="drop"),
        head=(buf.head + k) % cap,
        seen=buf.seen + k)


def buffer_add_masked(buf, kpms, iq, alloc, tp, mask):
    """Ring-ingest only the rows where ``mask`` is True (the slot-pool
    path: a churning fleet must not train on empty slots' zero samples).

    The write stays a fixed-shape scatter — invalid rows are dropped at
    the scatter, not gathered on the host — so one compiled program
    serves every occupancy level. Requires ``len(tp) <= capacity`` so the
    in-bounds indices stay unique (a slot pool's capacity is bounded by
    its replay ring's)."""
    cap = int(buf.tp.shape[0])
    n = int(np.shape(tp)[0])
    if n > cap:
        raise ValueError(
            f"masked ingest of {n} slots exceeds ring capacity {cap}; "
            "size OnlineConfig.capacity >= the slot-pool capacity")
    scatter = (_ring_scatter_masked_q if isinstance(buf, ReplayBufferQ)
               else _ring_scatter_masked)
    return scatter(buf, jnp.asarray(kpms, F32),
                   jnp.asarray(iq, F32),
                   jnp.asarray(alloc, F32),
                   jnp.asarray(tp, F32),
                   jnp.asarray(mask, bool))


@functools.partial(jax.jit, donate_argnums=0)
def _ring_scatter_ssm(buf: ReplayBufferSSM, state, feats,
                      tp) -> ReplayBufferSSM:
    # the recurrent ring's in-place write (see _ring_scatter)
    cap = buf.tp.shape[0]
    n = tp.shape[0]
    idx = (buf.head + jnp.arange(n, dtype=I32)) % cap
    return ReplayBufferSSM(
        state=buf.state.at[idx].set(state),
        feats=buf.feats.at[idx].set(feats),
        tp=buf.tp.at[idx].set(tp),
        head=(buf.head + n) % cap,
        seen=buf.seen + n)


@functools.partial(jax.jit, donate_argnums=0)
def _ring_scatter_masked_ssm(buf: ReplayBufferSSM, state, feats, tp,
                             mask) -> ReplayBufferSSM:
    # _ring_scatter_masked for the recurrent ring (slot-pool ingest:
    # cumsum-packed valid rows, invalid rows dropped at index ``cap``)
    cap = buf.tp.shape[0]
    m = mask.astype(I32)
    k = m.sum()
    pos = jnp.cumsum(m) - 1
    idx = jnp.where(mask, (buf.head + pos) % cap, cap)
    return ReplayBufferSSM(
        state=buf.state.at[idx].set(state, mode="drop"),
        feats=buf.feats.at[idx].set(feats, mode="drop"),
        tp=buf.tp.at[idx].set(tp, mode="drop"),
        head=(buf.head + k) % cap,
        seen=buf.seen + k)


def buffer_add_ssm(buf: ReplayBufferSSM, state, feats, tp,
                   mask=None) -> ReplayBufferSSM:
    """Ring-ingest N report events (pre-report state, features, label).

    ``mask`` selects live rows (the slot-pool path) through the packed
    fixed-shape scatter; without one, overflow keeps the newest
    ``capacity`` rows exactly like :func:`buffer_add`."""
    cap = int(buf.tp.shape[0])
    n = int(np.shape(tp)[0])
    if mask is not None:
        if n > cap:
            raise ValueError(
                f"masked ingest of {n} slots exceeds ring capacity {cap}; "
                "size OnlineConfig.capacity >= the slot-pool capacity")
        return _ring_scatter_masked_ssm(
            buf, jnp.asarray(state, F32), jnp.asarray(feats, F32),
            jnp.asarray(tp, F32), jnp.asarray(mask, bool))
    if n > cap:
        state, feats, tp = (x[-cap:] for x in (state, feats, tp))
    return _ring_scatter_ssm(buf, jnp.asarray(state, F32),
                             jnp.asarray(feats, F32), jnp.asarray(tp, F32))


def buffer_count(buf) -> int:
    """Valid rows in the ring (saturates at capacity)."""
    return int(min(int(buf.seen), buf.capacity))


def buffer_data(buf) -> dict:
    """The buffer as the dict ``make_indexed_step`` consumes.

    On the int8 ring the two big fields come out as ``(q, scales)``
    tuples; the trainer's in-program gather dequantizes exactly the
    minibatch rows it selects, never the whole ring."""
    if isinstance(buf, ReplayBufferQ):
        return {"kpms": (buf.kpms_q, buf.kpms_s),
                "iq": (buf.iq_q, buf.iq_s), "alloc": buf.alloc,
                "tp": buf.tp}
    if isinstance(buf, ReplayBufferSSM):
        return {"state": buf.state, "feats": buf.feats, "tp": buf.tp}
    return {"kpms": buf.kpms, "iq": buf.iq, "alloc": buf.alloc,
            "tp": buf.tp}


# ---------------------------------------------------------- drift monitor
@dataclasses.dataclass(frozen=True)
class DriftConfig:
    """EWMA drift monitor knobs (all units are Mbps of estimator RMSE)."""

    alpha: float = 0.25  # EWMA weight of the newest per-period RMSE
    calibrate_periods: int = 5  # healthy periods that set the baseline
    ratio: float = 1.5  # trigger level = ratio * calibrated baseline
    threshold_mbps: Optional[float] = None  # absolute override of ratio
    patience: int = 2  # consecutive above-threshold periods to fire
    cooldown: int = 3  # periods after a trigger before re-arming


@dataclasses.dataclass(frozen=True)
class DriftState:
    """Immutable monitor state; advance with :func:`drift_step`."""

    rmse_ewma: float = 0.0
    has_ewma: bool = False
    baseline: float = 0.0  # running mean RMSE of the calibration periods
    seen: int = 0  # periods consumed
    above: int = 0  # consecutive periods above threshold
    cooldown_left: int = 0
    n_triggers: int = 0


def drift_init() -> DriftState:
    return DriftState()


def drift_threshold(cfg: DriftConfig, state: DriftState) -> float:
    """The trigger level in Mbps: absolute if configured, else the
    calibrated ``ratio * baseline``."""
    if cfg.threshold_mbps is not None:
        return float(cfg.threshold_mbps)
    return cfg.ratio * max(state.baseline, 1e-6)


def drift_step(cfg: DriftConfig, state: DriftState, rmse_mbps: float,
               armed: bool = True) -> tuple[DriftState, bool]:
    """Feed one report period's estimator RMSE; returns (state, fired).

    The first ``calibrate_periods`` periods only calibrate the baseline
    (never fire). After that the EWMA must sit above the threshold for
    ``patience`` consecutive periods to fire — one noisy period is not
    drift — and a firing starts a ``cooldown`` during which the monitor is
    disarmed (the freshly adapted model needs periods to show its RMSE).

    ``armed=False`` means the caller cannot act on a trigger right now
    (the online loop passes this while the replay buffer is below
    ``min_fill``): the streak still builds but *holds* at ``patience``
    instead of firing — no cooldown is started and no trigger is consumed
    — so the first armed period with a held streak fires immediately."""
    rmse = float(rmse_mbps)
    a = cfg.alpha
    ewma = rmse if not state.has_ewma else a * rmse + (1 - a) * state.rmse_ewma
    seen = state.seen + 1
    if seen <= cfg.calibrate_periods:
        baseline = state.baseline + (rmse - state.baseline) / seen
        return dataclasses.replace(state, rmse_ewma=ewma, has_ewma=True,
                                   baseline=baseline, seen=seen), False
    if state.cooldown_left > 0:
        return dataclasses.replace(state, rmse_ewma=ewma, seen=seen,
                                   above=0,
                                   cooldown_left=state.cooldown_left - 1
                                   ), False
    above = state.above + 1 if ewma > drift_threshold(cfg, state) else 0
    if above >= cfg.patience:
        if not armed:  # hold the streak, don't consume the trigger
            return dataclasses.replace(state, rmse_ewma=ewma, seen=seen,
                                       above=cfg.patience), False
        return dataclasses.replace(state, rmse_ewma=ewma, seen=seen, above=0,
                                   cooldown_left=cfg.cooldown,
                                   n_triggers=state.n_triggers + 1), True
    return dataclasses.replace(state, rmse_ewma=ewma, seen=seen,
                               above=above), False


# --------------------------------------------------------- online trainer
@dataclasses.dataclass(frozen=True)
class OnlineConfig:
    """Knobs of the closed adaptation loop (see docs/online.md)."""

    capacity: int = 4096  # replay-buffer rows
    batch: int = 256  # minibatch rows per adaptation step
    steps: int = 20  # K jitted AdamW steps per trigger
    lr: float = 1e-3
    weight_decay: float = 1e-4
    clip_norm: float = 1.0
    min_fill: int = 256  # don't adapt before this many buffered rows
    seed: int = 0  # minibatch sampling + dropout keys
    ring_quant: Optional[str] = None  # "int8" stores replay samples
    # rowwise-quantized (~4x less ring memory; dequantized on the
    # trainer's minibatch gather). None keeps the exact fp32 ring.
    drift: DriftConfig = DriftConfig()
    ckpt_dir: Optional[str] = None  # CheckpointManager dir for adapted
    # weights; None disables checkpointing
    ckpt_keep: int = 3


@dataclasses.dataclass
class OnlineStats:
    """Host-side trace of one online episode (``FleetResult.online``)."""

    rmse: np.ndarray  # (T,) per-period estimator RMSE vs measured tp
    adapted: np.ndarray  # (T,) bool — an adaptation burst ran after t
    n_adaptations: int
    train_steps: int  # total jitted steps across all bursts
    train_loss: list  # mean minibatch loss per burst
    buffer_fill: int  # valid rows at episode end
    threshold_mbps: float  # the trigger level in effect at episode end
    params: object  # final (possibly adapted) estimator params
    ckpt_steps: list  # CheckpointManager steps written (empty without dir)


@functools.lru_cache(maxsize=None)
def online_step_program(ecfg: EstimatorConfig, opt: AdamW,
                        serving: Optional[ServingMesh]):
    """One compiled adaptation step per (estimator, optimizer, deployment)
    — the shared ``make_indexed_step`` factory, traced under the serving
    mesh when one is given so buffer minibatches shard over the data axis
    and the gradient psum is in the program. An ``SSMConfig`` takes the
    recurrent factory (``make_indexed_step_ssm``) — same calling
    convention, stored-state replay rows."""
    factory = (make_indexed_step_ssm if isinstance(ecfg, SSMConfig)
               else make_indexed_step)
    if serving is None:
        return factory(ecfg, opt)
    return factory(ecfg, opt, mesh=serving.mesh,
                   overrides=serving.rule_overrides())


def _place(x, axes):
    """``x`` as an f32 device array on the active serving mesh's sharding
    for these logical axes (``dist.sharding.put``; a plain device array
    with no mesh)."""
    return sh.put(jnp.asarray(x, F32), axes)


class OnlineAdapter:
    """The adaptation half every online loop shares: the batch and pool
    loops, windowed and recurrent, each bring their period's inputs, their
    predict call on :attr:`params`, their RMSE and their ring add; this
    owns the rest.

    That is the optimizer state and its compiled step
    (:func:`online_step_program`), the predict program for the
    estimator's kind (``fwd`` / ``ssm_step``, or their ``sim.serving``
    programs with the params replicated under a mesh), the replay ring,
    the drift monitor, the minibatch ``rng`` and dropout ``key`` (both
    seeded from ``ocfg.seed``), the optional ``CheckpointManager`` and the
    per-period trace that :meth:`finish` returns as :class:`OnlineStats`.
    Per period a loop calls :meth:`add` and then :meth:`observe`.

    ``telemetry``: an optional ``telemetry.HostTelemetry``; the adapter
    logs drift trigger/recovery, burst start/end and serving weight-swap
    events into its device ring."""

    def __init__(self, estimator, ocfg: OnlineConfig, t_steps: int, *,
                 serving: Optional[ServingMesh] = None, telemetry=None):
        ecfg, params = estimator
        self.ocfg, self.serving, self.telemetry = ocfg, serving, telemetry
        opt = AdamW(lr=ocfg.lr, weight_decay=ocfg.weight_decay,
                    clip_norm=ocfg.clip_norm)
        self.opt_state = opt.init(params)
        self.step_fn = online_step_program(ecfg, opt, serving)
        recurrent = isinstance(ecfg, SSMConfig)
        if serving is not None:
            self.predict = (ssm_serving_program if recurrent
                            else serving_program)(ecfg, serving)
            params = replicate_params(serving, params)
        else:
            self.predict = functools.partial(ssm_step if recurrent else fwd,
                                             ecfg)
        self.params = params
        self.mgr = (CheckpointManager(ocfg.ckpt_dir, keep=ocfg.ckpt_keep)
                    if ocfg.ckpt_dir else None)
        self.buf = buffer_init(ocfg.capacity, ecfg, serving=serving,
                               quant=ocfg.ring_quant)
        self.dstate = drift_init()
        self.rng = np.random.default_rng(ocfg.seed)
        self.key = jax.random.PRNGKey(ocfg.seed)
        self.rmse = np.zeros(t_steps)
        self.adapted = np.zeros(t_steps, bool)
        self.train_loss: list = []
        self.ckpt_steps: list = []
        self.total_steps = 0

    def rules(self):
        """The serving mesh's sharding rules as a context manager (a no-op
        without a mesh); the loop runs its periods inside it."""
        if self.serving is None:
            return contextlib.nullcontext()
        return sh.use_rules(self.serving.mesh, self.serving.rule_overrides())

    def add(self, buffer_add_fn, *rows, **kw):
        """Ring-ingest the period's rows with the loop's own
        ``buffer_add`` / ``buffer_add_masked`` / ``buffer_add_ssm``."""
        self.buf = buffer_add_fn(self.buf, *rows, **kw)

    def observe(self, t: int, rmse: float) -> bool:
        """Feed period ``t``'s estimator RMSE to the drift monitor and, when
        it fires, run one adaptation burst: ``ocfg.steps`` AdamW steps on
        ring minibatches, the weight swap under a mesh, the checkpoint.
        Returns whether period ``t`` adapted (:attr:`params` changed)."""
        o = self.ocfg
        self.rmse[t] = rmse
        fill = buffer_count(self.buf)
        # below min_fill the monitor holds its streak instead of consuming
        # the trigger: a drift detected before the buffer is ready fires
        # on the first period it can be acted on
        self.dstate, fired = drift_step(o.drift, self.dstate, rmse,
                                        armed=fill >= o.min_fill)
        if self.telemetry is not None:
            self.telemetry.drift(t, bool(fired), rmse,
                                 drift_threshold(o.drift, self.dstate),
                                 n_triggers=int(self.dstate.n_triggers))
        if not fired:
            return False
        data = buffer_data(self.buf)
        burst = []
        with telmod.stage("online_burst"):
            for _ in range(o.steps):
                idx = jnp.asarray(self.rng.integers(0, fill, o.batch), I32)
                self.key, sub = jax.random.split(self.key)
                self.params, self.opt_state, loss = self.step_fn(
                    self.params, self.opt_state, data, idx, sub)
                burst.append(float(loss))
            if self.serving is not None:
                # weight refresh: re-commit replicated so the next period's
                # predict is a compiled-program cache hit
                with telmod.stage("weight_swap"):
                    self.params = replicate_params(self.serving, self.params)
        self.total_steps += o.steps
        self.train_loss.append(float(np.mean(burst)))
        self.adapted[t] = True
        if self.telemetry is not None:
            self.telemetry.burst(t, o.steps, float(np.mean(burst)),
                                 self.serving is not None)
        if self.mgr is not None:
            self.mgr.save(self.dstate.n_triggers, self.params)  # async
            self.ckpt_steps.append(self.dstate.n_triggers)
        return True

    def finish(self) -> OnlineStats:
        """Wait for the checkpoint writes; the run's :class:`OnlineStats`."""
        if self.mgr is not None:
            self.mgr.wait()
        return OnlineStats(
            rmse=self.rmse, adapted=self.adapted,
            n_adaptations=int(self.adapted.sum()),
            train_steps=self.total_steps, train_loss=self.train_loss,
            buffer_fill=buffer_count(self.buf),
            threshold_mbps=drift_threshold(self.ocfg.drift, self.dstate),
            params=self.params, ckpt_steps=self.ckpt_steps)


def online_estimate_fleet(episode, estimator, ocfg: OnlineConfig, *,
                          serving: Optional[ServingMesh] = None,
                          tp_clip=TP_CLIP_MBPS, fused: bool = False,
                          telemetry=None
                          ) -> tuple[np.ndarray, OnlineStats]:
    """(N, T) Mbps estimates + :class:`OnlineStats`: the closed loop.

    Per 0.1 s report period: (1) predict the whole fleet's throughput with
    the *current* weights — the same per-period program ``sim.serving``
    caches, so refreshed weights are a cache hit, never a retrace; (2)
    observe the measured per-period throughput the engine emits
    (``engine.emit_period_samples``) and ring-ingest the (kpms, iq, alloc
    -> tp) rows; (3) feed the period RMSE to the drift monitor; (4) on a
    trigger, run ``ocfg.steps`` jitted AdamW steps on buffer minibatches,
    swap the updated weights into the serving path, and checkpoint them
    (steps 3-4 are :meth:`OnlineAdapter.observe`).

    The estimates returned are exactly what the controllers consume
    (clipped into ``tp_clip``); period t+1's estimate already reflects any
    adaptation period t triggered. Split decisions never feed back into
    the labels, so the engine can run its controller scan on the returned
    array afterwards — ``simulate_fleet(online=...)`` does exactly that,
    which keeps online composable with scheduling and fixed baselines.

    ``fused=True`` swaps the WINDOW x host window materialization for
    per-period views of the normalized KPM trace (bit-identical f32
    elements, see ``engine.emit_period_samples``).

    ``telemetry``: an optional ``telemetry.HostTelemetry`` — the loop logs
    drift trigger/recovery, burst start/end and serving weight-swap
    events into its device ring (the *metrics* accumulate later, in the
    engine's controller scan, so nothing is double counted). The returned
    values are unchanged.
    """
    from repro.sim.engine import emit_period_samples

    if isinstance(estimator[0], SSMConfig):
        # the recurrent loop: same drift monitor, same AdamW bursts, the
        # ring stores (pre-report state, report, label) events instead of
        # windows; ``fused`` is a no-op (nothing to featurize)
        return _online_estimate_fleet_ssm(episode, estimator, ocfg,
                                          serving=serving, tp_clip=tp_clip,
                                          telemetry=telemetry)
    if episode.iq is None:
        raise ValueError(
            "online adaptation needs IQ spectrograms: generate the episode "
            "with include_iq=True")
    n, t_steps = episode.n_ues, episode.n_steps
    if fused:
        wins = None
        trace = np.ascontiguousarray(
            kpmmod.normalize_kpms(episode.kpms).astype(np.float32))
    else:
        wins = episode.kpm_windows(normalize=True).astype(np.float32)
        trace = None
    ad = OnlineAdapter(estimator, ocfg, t_steps, serving=serving,
                       telemetry=telemetry)
    est = np.empty((n, t_steps))
    with ad.rules():
        alloc_d = _place(episode.alloc_ratio, ("batch",))
        for t in range(t_steps):
            s = emit_period_samples(episode, t, wins, trace=trace)
            kpms_t = _place(s["kpms"], ("batch", None, None))
            iq_t = _place(s["iq"], ("batch", None, None, None))
            with telmod.stage("estimator_fwd"):
                est[:, t] = np.clip(
                    np.asarray(ad.predict(ad.params, kpms_t, iq_t, alloc_d)),
                    tp_clip[0], tp_clip[1])
            tp_t = s["tp"]
            ad.add(buffer_add, kpms_t, iq_t, alloc_d, _place(tp_t, ("batch",)))
            ad.observe(t, float(np.sqrt(np.mean((est[:, t] - tp_t) ** 2))))
    return est, ad.finish()


def _online_estimate_fleet_ssm(episode, estimator, ocfg: OnlineConfig, *,
                               serving: Optional[ServingMesh] = None,
                               tp_clip=TP_CLIP_MBPS, telemetry=None
                               ) -> tuple[np.ndarray, OnlineStats]:
    """The recurrent arm of :func:`online_estimate_fleet`.

    Structurally the same closed loop with two differences born from the
    O(1) ingest. First, predict and observe are *one* program: the
    per-period ``ssm_step`` both advances each UE's recurrent state and
    emits its forecasts — there is no separate featurize stage, and each
    period costs the same whether the fleet has 30 or 30 000 reports of
    history (the first WINDOW - 1 trace columns run through the very same
    step program as label-free warmup). Second, the replay ring stores
    (pre-report state, report features, label) events; an adaptation
    burst replays single recurrence steps from those stored states
    (``estimator.train.ssm_step_loss``). The carried fleet states are
    *not* recomputed after a burst — they were built by older weights,
    and the recurrence's per-period decay forgets them at exp(dt*A);
    re-warming 30 columns per burst would reintroduce the O(WINDOW) cost
    this estimator exists to remove."""
    c = estimator[0]
    if episode.kpms is None:
        raise ValueError("the recurrent estimator needs raw KPM reports: "
                         "generate the episode with include_kpms=True")
    if c.include_iq and episode.iq is None:
        raise ValueError("SSMConfig(include_iq=True) needs spectrogram "
                         "snapshots: generate the episode with "
                         "include_iq=True")
    n, t_steps = episode.n_ues, episode.n_steps
    feats = episode_features(episode.kpms, episode.alloc_ratio,
                             episode.iq if c.include_iq else None)
    off = feats.shape[1] - t_steps - 1  # = WINDOW - 1, period 0's column
    ad = OnlineAdapter(estimator, ocfg, t_steps, serving=serving,
                       telemetry=telemetry)
    est = np.empty((n, t_steps))
    with ad.rules():
        state = _place(ssm_state_init(c, (n,)), STATE_AXES)
        for col in range(off):  # warmup reports precede the first label
            state, _ = ad.predict(ad.params, state,
                                  _place(feats[:, col], ("batch", None)))
        for t in range(t_steps):
            feats_t = _place(feats[:, off + t], ("batch", None))
            state_prev = state
            with telmod.stage("estimator_fwd"):
                state, fc = ad.predict(ad.params, state, feats_t)
                fc = np.asarray(fc)
            # the monitor watches the served *current* estimate's error;
            # the controllers consume the policy-reduced forecasts
            cur = np.clip(fc[:, 0], tp_clip[0], tp_clip[1])
            est[:, t] = np.clip(reduce_forecasts(c, fc),
                                tp_clip[0], tp_clip[1])
            tp_t = episode.tp_mbps[:, t].astype(np.float32)
            ad.add(buffer_add_ssm, state_prev, feats_t,
                   _place(tp_t, ("batch",)))
            ad.observe(t, float(np.sqrt(np.mean((cur - tp_t) ** 2))))
    return est, ad.finish()
