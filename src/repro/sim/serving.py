"""Mesh-sharded fleet estimator serving.

The one fleet layer PR 2/3 left unsharded was the per-report-period
estimator ``predict``: ``estimate_fleet`` ran the whole (N,) UE batch
through a single-device forward. This module runs that same forward as a
production-mesh SPMD program:

  * the UE batch (kpms window, IQ spectrogram, alloc ratio) is sharded
    over the mesh's ``data`` axis (and ``pod`` when present) through the
    ``batch`` rule of ``repro.dist.sharding`` — no new mechanism, the
    estimator's ``constrain`` annotations resolve against whatever mesh
    is active;
  * estimator weights stay replicated (their template axes are all
    ``None``), so per-period serving is pure data parallelism: zero
    cross-chip collectives in the forward, UE capacity scales linearly
    with chips until HBM/host bandwidth binds;
  * one per-report-period program is traced and compiled once per
    (estimator config, mesh, overrides, fleet shape) and reused for every
    report period of every episode batch — exactly the program an AF
    serving pod would run each 0.1 s tick.

Numerics: the sharded program computes the same per-UE forward as the
unsharded path (batch-only partitioning never re-associates a per-example
reduction), pinned allclose by ``tests/test_serving_mesh.py`` and the
``benchmarks/fleet.py --mesh`` sweep. The engine hook
(``estimate_fleet(..., serving=)``) therefore composes with
``simulate_fleet``/``run_scheduled`` without touching the sched=None
bit-identical guarantee, which only concerns the controller scan.

The slot-pool engine (``repro.sim.pool``) preserves this fixed-shape
contract under churn: its batch axis is the pool's ``capacity`` slots,
not the live population, so every per-period forward — frozen or online
— reuses the same compiled serving program at any occupancy; arrivals
and departures move the active mask, never the sharded shapes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.dist import sharding as sh
from repro.estimator.model import EstimatorConfig, estimator_forward
from repro.estimator.serve import (check_quant, estimator_forward_int8,
                                   quantize_estimator)
from repro.estimator.ssm import (SSMConfig, reduce_forecasts,
                                 ssm_state_init, ssm_step)
from repro.launch.mesh import make_host_mesh
from repro.sim import telemetry as telmod


@dataclasses.dataclass(frozen=True)
class ServingMesh:
    """A mesh + rule overrides describing one estimator-serving deployment.

    ``overrides`` are ``dist.sharding`` rule replacements stored as sorted
    (name, mesh-axes) pairs so the config stays hashable (it keys the
    compiled-program cache). The defaults already shard ``batch`` over
    ``("pod", "data")`` and replicate estimator weights, so most
    deployments pass no overrides at all.
    """

    mesh: jax.sharding.Mesh
    overrides: Tuple[Tuple[str, sh.MeshAxes], ...] = ()

    @property
    def n_chips(self) -> int:
        return self.mesh.size

    def rule_overrides(self) -> dict:
        return dict(self.overrides)

    def describe(self) -> str:
        """``data=4,model=2`` style axis summary for benchmark records."""
        return ",".join(f"{a}={s}" for a, s in self.mesh.shape.items())


def make_serving_mesh(spec: str = "1x1",
                      overrides: Optional[Mapping[str, sh.MeshAxes]] = None
                      ) -> ServingMesh:
    """Build a host-device ServingMesh from a ``DxM`` / ``DxExM`` string.

    Two factors are (data, model); three are (data, expert, model) — the
    EP variant that finally gives the reserved ``expert`` logical axis a
    physical home. Sizes are clamped to the host's device count with the
    same divisor-walking as ``make_host_mesh``, so any spec is
    constructible on any host (degrading to fewer shards, never erroring).
    """
    parts = [int(p) for p in spec.lower().split("x")]
    if len(parts) == 1:
        parts = [parts[0], 1]
    if len(parts) == 2:
        mesh = make_host_mesh(data=parts[0], model=parts[1])
    elif len(parts) == 3:
        mesh = make_host_mesh(data=parts[0], expert=parts[1], model=parts[2])
    else:
        raise ValueError(f"mesh spec {spec!r}: want DxM or DxExM")
    ov = tuple(sorted((overrides or {}).items()))
    return ServingMesh(mesh, ov)


@functools.lru_cache(maxsize=None)
def serving_program(ecfg: EstimatorConfig, serving: ServingMesh,
                    quant: Optional[str] = None):
    """The jitted per-report-period program for one deployment.

    Returns ``fn(params, kpms, iq, alloc) -> (N,) Mbps``. The serving
    ruleset is (re-)entered inside the traced function, so the estimator's
    ``constrain`` annotations bind to this deployment's mesh no matter
    when jit actually traces. Compiled once per input shape by jit's own
    cache; reused for every period.

    ``quant="int8"`` serves the int8 forward on a ``quantize_estimator``
    tree. GSPMD cannot partition a ``pallas_call``, so the mesh program
    takes the jnp oracle form (``use_kernel=False``) — bit-identical to
    the kernels, integer accumulation being exact (see
    ``estimator.serve``)."""
    check_quant(quant)
    mesh, overrides = serving.mesh, serving.rule_overrides()

    @jax.jit
    def fwd_sharded(params, kpms, iq, alloc):
        with sh.use_rules(mesh, overrides), \
                jax.named_scope("estimator_fwd"):
            if quant == "int8":
                return estimator_forward_int8(ecfg, params, kpms, iq, alloc,
                                              use_kernel=False)
            return estimator_forward(ecfg, params, kpms, iq, alloc)

    return fwd_sharded


def replicate_params(serving: ServingMesh, params):
    """Estimator params device-put replicated onto the deployment's mesh.

    This is also the whole weight-*refresh* path: ``serving_program``
    caches the compiled per-period program on (config, deployment) and
    takes the params as a runtime argument, so swapping adapted weights in
    — the ``repro.sim.online`` trainer does this after every adaptation
    burst — is one replicated ``device_put`` and a cache hit: no retrace,
    no recompile (the refreshed tree has the same shapes, dtypes and
    replicated sharding the program was compiled for).
    """
    return jax.device_put(params, NamedSharding(serving.mesh, P()))


def sharded_fleet_estimate(ecfg: EstimatorConfig, params, wins: np.ndarray,
                           iq: np.ndarray, alloc: np.ndarray,
                           serving: ServingMesh, tp_clip, *,
                           quant: Optional[str] = None,
                           window: Optional[int] = None) -> np.ndarray:
    """(N, T) Mbps: the mesh-sharded body of ``engine.estimate_fleet``.

    ``wins``: (N, T, WINDOW, 15) normalized KPM windows; ``iq``:
    (N, T, 2, n_sc, 14) spectrograms; ``alloc``: (N,) PRB ratios. Weights
    are replicated onto the mesh once; each period's slice is committed
    with the ``batch`` sharding (``dist.sharding.put``) and run through
    the cached per-period program.

    ``window``: the fused-featurize form — ``wins`` is then the
    (N, T + WINDOW, 15) *normalized trace* and period ``t``'s batch is the
    ``wins[:, t:t+window]`` view, so the (N, T, WINDOW, 15) window tensor
    is never materialized (same f32 elements, ~WINDOW x less memory).
    ``quant="int8"`` quantizes the weights once and serves the int8
    program (see ``serving_program``)."""
    check_quant(quant)
    n = wins.shape[0]
    t_steps = iq.shape[1]
    fn = serving_program(ecfg, serving, quant)
    if quant == "int8":
        # oracle quantizer: bit-identical to the kernel, and shardable
        params = quantize_estimator(params, use_kernel=False)
    params_r = replicate_params(serving, params)
    with sh.use_rules(serving.mesh, serving.rule_overrides()):
        with telmod.stage("estimator_fwd"), telmod.ingest() as put:
            alloc_d = sh.put(put(alloc, jnp.float32), ("batch",))
        est = np.empty((n, t_steps))
        for t in range(t_steps):
            with telmod.stage("estimator_fwd"):
                with telmod.ingest() as put:
                    win_t = (wins[:, t] if window is None
                             else wins[:, t:t + window])
                    kpms_t = sh.put(put(win_t), ("batch", None, None))
                    iq_t = sh.put(put(iq[:, t], jnp.float32),
                                  ("batch", None, None, None))
                est[:, t] = np.clip(np.asarray(fn(params_r, kpms_t, iq_t,
                                                  alloc_d)),
                                    tp_clip[0], tp_clip[1])
    return est


STATE_AXES = ("batch", None, None, None, None)  # per-UE recurrent state


@functools.lru_cache(maxsize=None)
def ssm_serving_program(c: SSMConfig, serving: ServingMesh):
    """The recurrent per-report-period program for one deployment.

    Returns ``fn(params, state, feats) -> (state, (N, K+1) forecasts)``
    — one O(1) SSD ingest step for the whole fleet, state and report
    batch sharded over the mesh's ``batch`` rule, weights replicated.
    ``ssm_step`` is pure jnp (no ``pallas_call``), which is what makes
    this program GSPMD-partitionable at all; the chunked SSD kernel only
    serves offline sequence passes. Weight refresh after an adaptation
    burst is the same ``replicate_params`` cache-hit path the windowed
    program uses."""
    mesh, overrides = serving.mesh, serving.rule_overrides()

    @jax.jit
    def ssm_step_sharded(params, state, feats):
        with sh.use_rules(mesh, overrides), \
                jax.named_scope("estimator_fwd"):
            return ssm_step(c, params, state, feats)

    return ssm_step_sharded


def sharded_ssm_estimate(c: SSMConfig, params, feats: np.ndarray,
                         serving: ServingMesh, tp_clip, *,
                         n_periods: int) -> np.ndarray:
    """(N, T) Mbps: the mesh-sharded recurrent body of
    ``engine.estimate_fleet``.

    ``feats``: the (N, S, F) report-stream features
    (``estimator.ssm.episode_features``; an EpisodeBatch trace has
    S = n_periods + WINDOW). Every report column — warmup included —
    runs through the *same* cached step program an AF pod would run each
    0.1 s tick; period ``t``'s estimate is emitted at column ``off + t``
    with ``off = S - n_periods - 1`` (= WINDOW - 1: the windowed path's
    alignment, the final report left unconsumed just as it is there).
    Pinned allclose to the unsharded sequence pass by
    ``tests/test_estimator_ssm.py``."""
    n, s = feats.shape[:2]
    off = s - n_periods - 1  # column of period 0's report
    fn = ssm_serving_program(c, serving)
    params_r = replicate_params(serving, params)
    est = np.empty((n, n_periods))
    with sh.use_rules(serving.mesh, serving.rule_overrides()):
        state = sh.put(ssm_state_init(c, (n,)), STATE_AXES)
        for col in range(off + n_periods):
            with telmod.stage("estimator_fwd"):
                with telmod.ingest() as put:
                    feats_t = sh.put(put(feats[:, col], jnp.float32),
                                     ("batch", None))
                state, fc = fn(params_r, state, feats_t)
                if col >= off:
                    est[:, col - off] = np.clip(
                        reduce_forecasts(c, np.asarray(fc)),
                        tp_clip[0], tp_clip[1])
    return est
