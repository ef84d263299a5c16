"""Smoke run of the report-period path on one TPU, through the library's
normal entry points, at the paper's widths.

  python chip_smoke.py              # one chip: phases (a), (b), (c)
  python chip_smoke.py --chips 4    # four chips: the mesh path only
  python chip_smoke.py --tiny       # any backend, tiny shapes: a rehearsal

Phases:
  (a) paper pipeline: VGG16 profile -> PSO table -> adaptive controller
      through a throughput collapse -> split inference of the full-width
      VGG16 (224x224, 1000 classes, batch 8) at the splits picked for 130
      and 8 Mbps; head o tail against the unsplit forward.
  (b) fleet report periods with the windowed estimator at the paper's
      width (n_sc=3276, hidden 124): simulate_fleet on the pure-XLA path,
      the fused-featurize path and the int8 path, each kernel against its
      jnp oracle on the chip.
  (c) recurrent (SSM) estimator + churned slot pool + 4 coupled cells under
      proportional-fair scheduling: kernels (ssd, segsum) against the jnp
      paths.
  --chips 4: the serving mesh (estimate_fleet, 4x1) and one sharded online
      step, each against its unsharded twin.

Every line prints what ran, its shapes, the first-call and repeat seconds
of a single run (a one-off smoke timing, not a benchmark) and the largest
difference from the oracle. The last line of a passing chip run is one JSON
object naming the device; the script exits non-zero, without that line,
when the device is not a TPU or any check fails.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Tolerances, each set from what one TPU v5e showed at seed 0, with the
# margin stated. Integer paths (quantize codes, the int8 forward) and the
# sharded online loss read exactly equal there, and must stay so.
# The same f32 ops in two programs (head o tail vs the unsplit VGG16, the
# featurize kernel vs its oracle): 0.0 on the chip; a few f32 ulps of the
# output's largest value.
ELEMWISE_REL_TOL = 2.0 ** -20
# Fused vs XLA windowed-estimator engine, both at the default precision
# (bf16 operands): 0.0047 Mbps on the chip; 10x.
FUSED_MBPS_TOL = 0.05
# Two fp32 paths that sum in different orders (ssd kernel vs jnp, segsum
# kernel vs segment_sum): at most 5.5e-7 of the largest output on the
# chip; 14x.
FP32_REL_TOL = 2.0 ** -17
# Sharded vs unsharded estimate_fleet at the default precision: 0.037 Mbps
# on four chips; 14x.
MESH_MBPS_TOL = 0.5
# Sharded vs unsharded AdamW first moments after one step: 6.9e-7 of the
# largest on four chips (1.9e-6 with a narrower readout); 8x the larger.
MESH_GRAD_REL_TOL = 2.0 ** -16
# Share of estimates strictly inside the table's range that a phase needs
# before its comparisons count: the rest sit at the clip and hide faults.
MIN_UNCLIPPED = 0.9
# VGG16 ends fc -> softmax -> output: ops [0, 41) end in the logits.
LOGITS_OP = 41
# Quickstart's throughput collapse (Mbps per 0.1 s report).
COLLAPSE = (120, 118, 95, 60, 22, 9, 8, 7, 9, 8)


class Checks:
    """Collects one line per check; ``ok`` is False once any check fails."""

    def __init__(self):
        self.ok = True

    def line(self, phase: str, what: str, *, diff=None, tol=None,
             passed: bool | None = None, **info):
        if passed is None:
            passed = diff is None or (diff == diff and diff <= tol)
        self.ok &= bool(passed)
        parts = [f"[{phase}] {what}"]
        parts += [f"{k}={_fmt(v)}" for k, v in info.items()]
        if diff is not None:
            parts.append(f"max_abs_diff={diff!r} tol={tol!r}")
        parts.append(f"ok={bool(passed)}")
        print(" ".join(parts), flush=True)


def _fmt(v):
    return repr(v) if isinstance(v, float) else str(v)


def _range(x) -> tuple:
    import numpy as np
    x = np.asarray(x, np.float64)
    return (float(np.nanmin(x)), float(np.nanmax(x)))


def timed(fn):
    """(result, first-call seconds, repeat seconds) of ``fn()``, whose
    result is host data or device arrays waited on."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    jax.block_until_ready(fn())
    return out, t1 - t0, time.perf_counter() - t1


def max_abs(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape or not np.array_equal(np.isnan(a), np.isnan(b)):
        return float("inf")
    d = np.abs(a - b)
    return float(np.nanmax(d)) if d.size else 0.0


def rel_tol(ref, rel: float) -> float:
    """``rel`` of the largest magnitude in ``ref``."""
    import numpy as np
    r = np.abs(np.asarray(ref, np.float64))
    return rel * (float(np.nanmax(r)) if r.size else 0.0)


def has_kernel(jitted, *args, **kwargs) -> bool:
    """The compiled program of ``jitted`` at these arguments holds a Mosaic
    kernel (not the interpreter's loop)."""
    return "tpu_custom_call" in jitted.lower(*args, **kwargs).compile(
    ).as_text()


def into_table_range(params, raw):
    """Random estimator weights with the readout's last layer mapped
    affinely so that ``raw`` (its unclipped output on this run's inputs)
    has its 1st and 99th percentiles at 5 and 125 Mbps: the estimates then
    spread over the PSO table's range instead of sitting at the clip."""
    import numpy as np
    lo, hi = (float(v) for v in np.percentile(np.asarray(raw, np.float64),
                                              [1, 99]))
    a = 120.0 / (hi - lo)
    head = params["head"]
    return dict(params, head=dict(head, w2=head["w2"] * a,
                                  b2=(head["b2"] - lo) * a + 5.0))


def unclipped_share(est) -> float:
    """Share of estimates strictly inside the table's range."""
    import numpy as np

    from repro.core.pso import TP_CLIP_MBPS
    est = np.asarray(est)
    return float(np.mean((est > TP_CLIP_MBPS[0]) & (est < TP_CLIP_MBPS[1])))


def raw_estimates(episode, estimator):
    """``estimate_fleet`` without the clip into the table's range."""
    import numpy as np

    from repro.sim import estimate_fleet
    return estimate_fleet(episode, estimator, tp_clip=(-np.inf, np.inf))


def paper_setup():
    from repro.core.energy import EDGE_A40X2, UE_VM_2CORE
    from repro.core.objective import Constraints, Weights
    from repro.core.pso import pso_vectorized
    from repro.models.vgg import FULL, vgg_split_profile
    profile = vgg_split_profile(FULL)
    table = pso_vectorized(
        profile, UE_VM_2CORE, EDGE_A40X2,
        Weights(w_delay=1.0, w_privacy=0.15, w_energy=0.1),
        Constraints(rho_max=0.92, tau_max_s=6.0, e_max_j=40.0),
        tp_max_mbps=130)
    return profile, table


def phase_paper(ck: Checks, seed: int, tiny: bool, profile, table):
    """(a) profile -> PSO -> controller -> split VGG16 inference."""
    import jax
    import numpy as np

    from repro.core import boundary
    from repro.core.controller import AdaptiveSplitController
    from repro.core.splitting import vgg_head, vgg_tail
    from repro.models.vgg import FULL, REDUCED, forward, init_vgg

    ctl = AdaptiveSplitController(table)
    traj = [ctl.update(tp) for tp in COLLAPSE]
    ck.line("a", "controller through a throughput collapse",
            passed=all(-1 <= s < profile.n_splits for s in traj),
            reports_mbps=list(COLLAPSE), splits=traj)
    vcfg = REDUCED if tiny else FULL
    params = jax.jit(lambda k: init_vgg(vcfg, k))(jax.random.PRNGKey(seed))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (8, vcfg.image_size, vcfg.image_size, 3))
    full = jax.jit(lambda p, im: forward(vcfg, p, im))
    ref, f_first, f_rep = timed(lambda: full(params, x))
    logits = np.asarray(jax.jit(lambda p, im: forward(
        vcfg, p, im, stop=LOGITS_OP))(params, x))
    ck.line("a", "vgg16 unsplit forward", passed=bool(
        np.isfinite(np.asarray(ref)).all()), shape=tuple(ref.shape),
        probs=_range(ref), first_call_s=f_first, repeat_s=f_rep)
    for tp in (130, 8):
        l = table.query(tp)
        if l < 0:  # NO_SPLIT: the whole model runs at the edge
            ck.line("a", f"table at {tp} Mbps picks no split",
                    passed=True, split=l)
            continue
        head = jax.jit(lambda p, im, l=l: vgg_head(vcfg, p, im, l))
        tail = jax.jit(lambda p, a, l=l: vgg_tail(vcfg, p, a, l))
        codec = jax.jit(lambda a: boundary.roundtrip(a, boundary.INT8))
        out, first, rep = timed(lambda: tail(params, head(params, x)))
        ck.line("a", f"head o tail at {tp} Mbps vs unsplit forward",
                diff=max_abs(out, ref), tol=rel_tol(ref, ELEMWISE_REL_TOL),
                split=l,
                first_call_s=first, repeat_s=rep)
        if l <= LOGITS_OP:  # random weights saturate the softmax
            tail_logits = np.asarray(jax.jit(lambda p, a, l=l: forward(
                vcfg, p, a, start=l, stop=LOGITS_OP))(params,
                                                      head(params, x)))
            ck.line("a", f"head o tail logits at {tp} Mbps vs unsplit",
                    diff=max_abs(tail_logits, logits),
                    tol=rel_tol(logits, ELEMWISE_REL_TOL))
        act = head(params, x)
        q_out = np.asarray(tail(params, codec(act)))
        ck.line("a", f"head -> INT8 codec -> tail at {tp} Mbps",
                passed=bool(np.isfinite(q_out).all()
                            and np.allclose(q_out.sum(-1), 1.0, atol=1e-3)),
                boundary_shape=tuple(act.shape),
                int8_vs_fp32_max_abs=max_abs(q_out, ref))


def phase_fleet(ck: Checks, seed: int, tiny: bool, profile, table):
    """(b) simulate_fleet with the paper-width windowed estimator."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.channel import kpm as kpmmod
    from repro.channel.scenarios import SCENARIOS, WINDOW, gen_episode_batch
    from repro.core.controller import ControllerConfig
    from repro.estimator.model import EstimatorConfig, init_estimator
    from repro.estimator.serve import fwd_int8, quantize_estimator
    from repro.estimator.train import fwd
    from repro.kernels.featurize import kpm_feature_windows
    from repro.sim import simulate_fleet
    from repro.sim.engine import est_chunk_rows

    ecfg = EstimatorConfig(n_sc=64) if tiny else EstimatorConfig()
    n, t_steps = (8, 4) if tiny else (est_chunk_rows(ecfg), 8)
    rng = np.random.default_rng(seed)
    scen = np.asarray(SCENARIOS)[np.arange(n) % len(SCENARIOS)]
    t0 = time.perf_counter()
    ep = gen_episode_batch(scen, t_steps, rng, n_sc=ecfg.n_sc)
    ck.line("b", "host episode generation", passed=True,
            iq_shape=ep.iq.shape, iq_gib=ep.iq.nbytes / 2 ** 30,
            seconds=time.perf_counter() - t0,
            chunk_rows=est_chunk_rows(ecfg))
    params = init_estimator(ecfg, jax.random.PRNGKey(seed + 2))
    params = into_table_range(params, raw_estimates(ep, (ecfg, params)))
    cfg = ControllerConfig()
    res = {}
    for name, kw in (("xla", {}), ("fused", {"fused": True}),
                     ("int8", {"quant": "int8"})):
        r, first, rep = timed(lambda kw=kw: simulate_fleet(
            ep, table, profile, cfg, estimator=(ecfg, params), **kw))
        res[name] = r
        inside = unclipped_share(r.est_tp)
        ck.line("b", f"simulate_fleet {name}", passed=bool(
            np.isfinite(r.est_tp).all() and r.splits.shape == (n, t_steps)
            and inside >= MIN_UNCLIPPED),
            est_shape=r.est_tp.shape, est_mbps=_range(r.est_tp),
            unclipped_share=inside, distinct_splits=len(np.unique(r.splits)),
            first_call_s=first, repeat_s=rep)
    base = res["xla"].est_tp
    ck.line("b", "fused engine estimates vs xla engine estimates (Mbps)",
            diff=max_abs(res["fused"].est_tp, base), tol=FUSED_MBPS_TOL)
    ck.line("b", "int8 engine estimates vs fp32 (Mbps, accuracy, no tol)",
            passed=True, rmse=float(np.sqrt(np.mean(
                (res["int8"].est_tp - base) ** 2))))

    # kernels against their jnp oracles on one chunk's rows
    rows = min(n, est_chunk_rows(ecfg))
    slab = jnp.asarray(ep.kpms[:rows, :WINDOW], jnp.float32)
    center, scale = jnp.asarray(kpmmod.KPM_CENTER), jnp.asarray(
        kpmmod.KPM_SCALE)
    kw_k = kpm_feature_windows(slab, center, scale, window=WINDOW)
    kw_o = kpm_feature_windows(slab, center, scale, window=WINDOW,
                               use_kernel=False)
    ck.line("b", "featurize kernel vs oracle", diff=max_abs(kw_k, kw_o),
            tol=rel_tol(kw_o, ELEMWISE_REL_TOL), shape=kw_k.shape)
    kpms_rows = kw_k.reshape(rows, WINDOW, -1)
    iq_rows = jnp.asarray(ep.iq[:rows, 0], jnp.float32)
    alloc_rows = jnp.asarray(ep.alloc_ratio[:rows], jnp.float32)
    q_k = quantize_estimator(params)
    q_o = quantize_estimator(params, use_kernel=False)
    qd = max(int(np.abs(np.asarray(a, np.int32) - np.asarray(b, np.int32)
                        ).max()) for a, b in zip(jax.tree.leaves(q_k),
                                                 jax.tree.leaves(q_o))
             if a.dtype == jnp.int8)
    sd = max(max_abs(a, b) for a, b in zip(jax.tree.leaves(q_k),
                                           jax.tree.leaves(q_o))
             if a.dtype != jnp.int8)
    ck.line("b", "quantize kernel vs oracle (int8 codes, scales)",
            passed=qd == 0 and sd == 0.0, max_code_diff=qd,
            max_scale_diff=sd, fc_width=ecfg.cnn_flat)
    o_k = fwd_int8(ecfg, q_o, kpms_rows, iq_rows, alloc_rows)
    o_o = fwd_int8(ecfg, q_o, kpms_rows, iq_rows, alloc_rows,
                   use_kernel=False)
    ck.line("b", "int8 forward (lstm_scan_q + qmm) vs oracle (Mbps, raw)",
            diff=max_abs(o_k, o_o), tol=0.0, rows=rows)
    o32 = fwd(ecfg, params, kpms_rows, iq_rows, alloc_rows)
    ck.line("b", "fp32 forward on the same rows (raw Mbps, no tol)",
            passed=bool(np.isfinite(np.asarray(o32)).all()),
            out_mbps=_range(o32))
    if jax.devices()[0].platform == "tpu":
        ck.line("b", "fused/int8 programs hold Mosaic kernels", passed=(
            has_kernel(kpm_feature_windows, slab, center, scale,
                       window=WINDOW)
            and has_kernel(fwd_int8, ecfg, q_o, kpms_rows, iq_rows,
                           alloc_rows)))


def phase_ssm(ck: Checks, seed: int, tiny: bool, profile, table):
    """(c) SSM estimator + churn + 4 coupled cells under PF."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.channel.scenarios import (SCENARIOS, WINDOW, ChurnConfig,
                                         make_churn_schedule)
    from repro.core.controller import ControllerConfig
    from repro.estimator.ssm import (SSMConfig, episode_features, init_ssm,
                                     ssm_forward_seq)
    from repro.kernels.segsum import segment_reduce
    from repro.sim import (SchedulerConfig, attach_ring, build_cells_episode,
                           ring_coupling, simulate_fleet)

    n_cells = 4
    capacity, t_steps = (64, 10) if tiny else (1024, 40)
    rng = np.random.default_rng(seed + 3)
    ccfg = ChurnConfig(arrival_rate=0.1 * capacity, mean_dwell=8.5,
                       diurnal_amplitude=0.25, diurnal_period=t_steps)
    schedule = make_churn_schedule(ccfg, t_steps, rng)
    m, dwell = schedule.n_sessions, schedule.max_dwell
    attach = attach_ring(m, n_cells)
    grid = np.repeat(attach[:, None], dwell + WINDOW, axis=1)
    scen = np.asarray(SCENARIOS)[np.arange(m) % len(SCENARIOS)]
    sessions = build_cells_episode(scen, dwell, rng, grid,
                                   coupling=ring_coupling(n_cells))
    s_kernel, s_oracle = SSMConfig(use_kernel=True), SSMConfig()
    sparams = init_ssm(s_kernel, jax.random.PRNGKey(seed + 4))
    sparams = into_table_range(sparams,
                               raw_estimates(sessions, (s_oracle, sparams)))
    raw_k = raw_estimates(sessions, (s_kernel, sparams))
    raw_o = raw_estimates(sessions, (s_oracle, sparams))
    d, tol, inside = (max_abs(raw_k, raw_o), rel_tol(raw_o, FP32_REL_TOL),
                      unclipped_share(raw_o))
    ck.line("c", "unclipped estimates kernel vs oracle (Mbps)", diff=d,
            tol=tol, passed=d <= tol and inside >= MIN_UNCLIPPED,
            shape=raw_o.shape, est_mbps=_range(raw_o),
            unclipped_share=inside)
    cfg = ControllerConfig()
    res = {}
    for name, scfg, fused in (("kernel", s_kernel, True),
                              ("oracle", s_oracle, False)):
        r, first, rep = timed(
            lambda scfg=scfg, fused=fused: simulate_fleet(
                sessions, table, profile, cfg, estimator=(scfg, sparams),
                churn=schedule, capacity=capacity,
                sched=SchedulerConfig(policy="pf", fused=fused),
                cell_idx=attach, n_cells=n_cells))
        res[name] = r
        # est_tp is what the controllers consumed: the estimate times the
        # UE's PRB share of its cell
        ck.line("c", f"simulate_fleet ssm+churn+pf {name}",
                passed=bool(r.active.any()), slots=capacity,
                periods=t_steps, sessions=m,
                shared_est_mbps=_range(r.est_tp[r.active]),
                distinct_splits=len(np.unique(r.splits[r.active])),
                first_call_s=first, repeat_s=rep)
    k, o = res["kernel"], res["oracle"]
    ck.line("c", "splits kernel vs oracle (exact)", passed=bool(
        np.array_equal(k.splits, o.splits)),
        n_differ=int((k.splits != o.splits).sum()))
    ck.line("c", "shared estimates kernel vs oracle (Mbps)",
            diff=max_abs(k.est_tp, o.est_tp),
            tol=rel_tol(o.est_tp, FP32_REL_TOL))
    ck.line("c", "PRB shares fused vs segment_sum",
            diff=max_abs(k.prb_share, o.prb_share),
            tol=rel_tol(o.prb_share, FP32_REL_TOL))
    feats = jnp.asarray(episode_features(sessions.kpms,
                                         sessions.alloc_ratio, None))
    fc_k, _ = ssm_forward_seq(s_kernel, sparams, feats)
    fc_o, _ = ssm_forward_seq(s_oracle, sparams, feats)
    ck.line("c", "ssd kernel forecasts vs oracle at every report (raw)",
            diff=max_abs(fc_k, fc_o), tol=rel_tol(fc_o, FP32_REL_TOL),
            feats_shape=feats.shape)
    if jax.devices()[0].platform == "tpu":
        vals = jnp.asarray(rng.uniform(size=(1, capacity)), jnp.float32)
        seg = jnp.asarray(attach_ring(capacity, n_cells)[None], jnp.int32)
        ck.line("c", "ssd/segsum programs hold Mosaic kernels", passed=(
            has_kernel(ssm_forward_seq, s_kernel, sparams, feats)
            and has_kernel(segment_reduce, vals, seg, n_cells)))


def phase_mesh(ck: Checks, seed: int, tiny: bool):
    """--chips 4: serving mesh + one sharded online step vs unsharded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.channel.scenarios import SCENARIOS, gen_episode_batch
    from repro.estimator.model import EstimatorConfig, init_estimator
    from repro.estimator.train import make_indexed_step
    from repro.optim import AdamW
    from repro.sim import estimate_fleet, make_serving_mesh

    serving = make_serving_mesh("4x1")
    devs = set(serving.mesh.devices.flat)
    ck.line("mesh", "serving mesh 4x1", passed=(
        dict(serving.mesh.shape) == {"data": 4, "model": 1}
        and len(devs) == 4), shape=dict(serving.mesh.shape),
        devices=sorted(d.id for d in devs))
    ecfg = EstimatorConfig(n_sc=64) if tiny else EstimatorConfig()
    n, t_steps = (8, 4) if tiny else (256, 4)
    rng = np.random.default_rng(seed)
    scen = np.asarray(SCENARIOS)[np.arange(n) % len(SCENARIOS)]
    ep = gen_episode_batch(scen, t_steps, rng, n_sc=ecfg.n_sc)
    params = init_estimator(ecfg, jax.random.PRNGKey(seed + 2))
    params = into_table_range(params, raw_estimates(ep, (ecfg, params)))
    plain, p_first, p_rep = timed(lambda: estimate_fleet(ep, (ecfg, params)))
    shard, s_first, s_rep = timed(lambda: estimate_fleet(
        ep, (ecfg, params), serving=serving))
    d, inside = max_abs(shard, plain), unclipped_share(plain)
    ck.line("mesh", "estimate_fleet sharded vs unsharded (Mbps)", diff=d,
            tol=MESH_MBPS_TOL,
            passed=d <= MESH_MBPS_TOL and inside >= MIN_UNCLIPPED,
            fleet=(n, t_steps), unclipped_share=inside, unsharded_first_s=p_first,
            unsharded_repeat_s=p_rep, sharded_first_s=s_first,
            sharded_repeat_s=s_rep)
    rows = min(64, n * t_steps)
    data = {"kpms": jnp.asarray(rng.normal(size=(rows, ecfg.window,
                                                 ecfg.n_kpms)), jnp.float32),
            "iq": jnp.asarray(ep.iq.reshape(-1, *ep.iq.shape[2:])[:rows],
                              jnp.float32),
            "alloc": jnp.asarray(rng.uniform(size=rows), jnp.float32),
            "tp": jnp.asarray(rng.uniform(10, 100, rows), jnp.float32)}
    idx = jnp.asarray(rng.integers(0, rows, 32), jnp.int32)
    key = jax.random.PRNGKey(seed + 5)
    opt = AdamW(lr=1e-3, weight_decay=1e-4, clip_norm=1.0)
    step_u = make_indexed_step(ecfg, opt)
    step_s = make_indexed_step(ecfg, opt, mesh=serving.mesh,
                               overrides=serving.rule_overrides())
    (pu, ou, lu), u_first, u_rep = timed(
        lambda: step_u(params, opt.init(params), data, idx, key))
    (ps, os_, ls), sh_first, sh_rep = timed(
        lambda: step_s(params, opt.init(params), data, idx, key))
    ck.line("mesh", "online step sharded vs unsharded (loss)",
            diff=abs(float(lu) - float(ls)), tol=0.0,
            batch=int(idx.shape[0]),
            unsharded_first_s=u_first, unsharded_repeat_s=u_rep,
            sharded_first_s=sh_first, sharded_repeat_s=sh_rep)
    # after one step AdamW's first moment is (1 - b1) x the clipped
    # gradient: the psum'd gradient against the single-device one. (The
    # updated weights move by about lr whatever the gradient's size, so a
    # near-zero gradient of either sign moves them apart by up to 2 lr.)
    g_u, g_s = jax.tree.leaves(ou["m"]), jax.tree.leaves(os_["m"])
    ck.line("mesh", "online step sharded vs unsharded (gradient moments)",
            diff=max(max_abs(a, b) for a, b in zip(g_u, g_s)),
            tol=MESH_GRAD_REL_TOL * max(float(np.abs(np.asarray(a)).max())
                                        for a in g_u),
            params_max_abs_diff=max(max_abs(a, b) for a, b in zip(
                jax.tree.leaves(pu), jax.tree.leaves(ps))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and traffic")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip mesh path")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal at tiny shapes on any backend; prints "
                         "no device result")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if not args.tiny and platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    print(f"# chip_smoke on {platform} {devices[0].device_kind} x"
          f"{len(devices)}, jax {jax.__version__}, seed {args.seed}",
          flush=True)
    ck = Checks()
    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(ck, args.seed, args.tiny))]
    else:
        profile, table = paper_setup()
        phases = [(name, lambda f=f: f(ck, args.seed, args.tiny, profile,
                                       table))
                  for name, f in (("a", phase_paper), ("b", phase_fleet),
                                  ("c", phase_ssm))]
    for name, run in phases:
        try:
            run()
        except Exception:  # report every phase, then fail the run
            traceback.print_exc()
            ck.line(name, "phase raised", passed=False)
    if not ck.ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    if args.tiny:
        print(f"chip_smoke: tiny rehearsal passed on {platform} "
              "(not a chip result)")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
