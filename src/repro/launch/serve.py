"""Split-serving launcher: the paper's UE/edge boundary at pod scale.

--dry-run: builds the multi-pod production mesh, slices it into the UE pod
(pod 0) and edge pod (pod 1), lowers + compiles the HEAD program on the UE
submesh and the TAIL program on the edge submesh for every valid split
point, and reports the boundary traffic per codec. This is deliverable (e)'s
split-serving mode: two runtimes + an explicit inter-pod link, exactly how
a disaggregated deployment runs.

--fleet-estimator N: instead AOT-lowers the mesh-sharded fleet estimator
serving program (``repro.sim.serving``) for an N-UE report period on the
single-pod production mesh (``--ep`` swaps in the expert-parallel
``data x expert x model`` variant) and reports the batch sharding, UEs
per chip, and compiled memory footprint.

--fleet-online N: AOT-lowers one online *adaptation* step
(``repro.sim.online``: replay-buffer gather + estimator fwd/bwd + AdamW)
against an N-row buffer on the production mesh — buffer rows sharded over
the data axis, params/optimizer moments replicated — and reports the
minibatch sharding, whether the gradient all-reduce (psum) made it into
the program, and the compiled memory footprint.

Usage:
  python -m repro.launch.serve --dry-run --arch granite-8b --split 18
  python -m repro.launch.serve --fleet-estimator 4096 [--ep]
  python -m repro.launch.serve --fleet-online 65536 [--online-batch 4096]
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

# ruff: noqa: E402
import argparse
import json

import jax

# AOT lowering on virtual host devices: never open an accelerator, so a chip
# on this host stays with the process that serves on it.
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs import get_config
from repro.core import boundary
from repro.core.splitting import lm_head, lm_split_points, lm_tail
from repro.dist import sharding as sh
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (COMPILER_OPTS, serve_overrides,
                                serve_param_template)
from repro.models import abstract_params
from repro.models.template import shardings_from_template


def pod_submesh(mesh, pod: int) -> Mesh:
    return Mesh(mesh.devices[pod], ("data", "model"))


def fleet_estimator_dryrun(n_ues: int, ep: bool) -> None:
    """Lower + compile one mesh-sharded estimator report period (AOT)."""
    from repro.estimator.model import EstimatorConfig, estimator_template
    from repro.models import template as T
    from repro.sim.serving import ServingMesh, serving_program

    e = EstimatorConfig()
    mesh = make_production_mesh(ep=ep)
    serving = ServingMesh(mesh)
    fn = serving_program(e, serving)
    pabs = T.abstract_from_template(estimator_template(e))
    kpms = jax.ShapeDtypeStruct((n_ues, e.window, e.n_kpms), jnp.float32)
    iq = jax.ShapeDtypeStruct((n_ues, 2, e.n_sc, e.n_sym), jnp.float32)
    alloc = jax.ShapeDtypeStruct((n_ues,), jnp.float32)
    compiled = fn.lower(pabs, kpms, iq, alloc).compile(COMPILER_OPTS)
    # resolve the batch sharding the program actually gets: a fleet size
    # not divisible by the data axes falls back to replicated (Ruleset
    # rule 2), and the report must say so rather than claim shards
    rs = sh.Ruleset(mesh, dict(sh.DEFAULT_RULES))
    entry = rs.spec(("batch", None, None), kpms.shape)[0]
    axes = (() if entry is None else
            (entry,) if isinstance(entry, str) else entry)
    batch_shards = 1
    for a in axes:
        batch_shards *= mesh.shape[a]
    print(json.dumps({
        "mode": "fleet-estimator", "mesh": dict(mesh.shape),
        "chips": mesh.size, "n_ues": n_ues,
        "batch_sharded": batch_shards > 1,
        "batch_shards": batch_shards,
        "rows_per_shard": n_ues // batch_shards,
        # with the batch replicated every chip computes the whole fleet,
        # so the per-chip capacity accounting only holds when sharded
        "ue_per_chip": (round(n_ues / mesh.size, 2) if batch_shards > 1
                        else float(n_ues)),
        "memory": str(compiled.memory_analysis()),
    }, indent=1))


def fleet_online_dryrun(n_rows: int, batch: int, ep: bool) -> None:
    """Lower + compile one mesh-sharded online adaptation step (AOT)."""
    from repro.estimator.model import EstimatorConfig, estimator_template
    from repro.estimator.train import make_indexed_step
    from repro.models import template as T
    from repro.optim import AdamW
    from repro.sim.serving import ServingMesh

    e = EstimatorConfig()
    mesh = make_production_mesh(ep=ep)
    serving = ServingMesh(mesh)
    opt = AdamW(lr=1e-3, weight_decay=1e-4, clip_norm=1.0)
    step = make_indexed_step(e, opt, mesh=mesh,
                             overrides=serving.rule_overrides())
    pabs = T.abstract_from_template(estimator_template(e))
    opt_abs = jax.eval_shape(opt.init, pabs)
    rs = sh.Ruleset(mesh, dict(sh.DEFAULT_RULES))

    # buffer rows committed batch-sharded, like sim.online.buffer_init
    def rows(shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.float32,
            sharding=rs.sharding(("batch",) + (None,) * (len(shape) - 1),
                                 shape))
    data = {"kpms": rows((n_rows, e.window, e.n_kpms)),
            "iq": rows((n_rows, 2, e.n_sc, e.n_sym)),
            "alloc": rows((n_rows,)), "tp": rows((n_rows,))}
    idx = jax.ShapeDtypeStruct((batch,), jnp.int32)
    key_abs = jax.eval_shape(jax.random.PRNGKey, 0)
    lowered = step.lower(pabs, opt_abs, data, idx, key_abs)
    compiled = lowered.compile(COMPILER_OPTS)
    # the gradient psum is inserted by SPMD partitioning, so it only shows
    # in the compiled (post-partitioning) HLO, not the lowering
    try:
        text = compiled.as_text()
    except Exception:  # pragma: no cover - backend without HLO dump
        text = ""
    spec = rs.spec(("batch", None, None, None), data["iq"].shape)[0]
    axes = (() if spec is None else
            (spec,) if isinstance(spec, str) else spec)
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    print(json.dumps({
        "mode": "fleet-online", "mesh": dict(mesh.shape),
        "chips": mesh.size, "buffer_rows": n_rows, "batch": batch,
        "buffer_sharded": shards > 1, "buffer_shards": shards,
        "rows_per_shard": n_rows // shards,
        # the data-parallel gradient psum must be in the program, or the
        # "sharded == unsharded" trainer contract is silently broken
        "grads_psummed": ("all-reduce" in text or "all_reduce" in text),
        "memory": str(compiled.memory_analysis()),
    }, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--split", type=int, default=None,
                    help="megablock split index (default: middle)")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--codec", default="int8", choices=["fp16", "int8", "int4"])
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--fleet-estimator", type=int, default=0, metavar="N",
                    help="AOT-lower the mesh-sharded fleet estimator "
                    "serving program for an N-UE report period instead of "
                    "the split-serving dry-run")
    ap.add_argument("--fleet-online", type=int, default=0, metavar="N",
                    help="AOT-lower one online adaptation step (buffer "
                    "gather + fwd/bwd + AdamW) against an N-row replay "
                    "buffer on the production mesh")
    ap.add_argument("--online-batch", type=int, default=4096,
                    help="minibatch rows for --fleet-online")
    ap.add_argument("--ep", action="store_true",
                    help="use the expert-parallel production mesh variant "
                    "(data x expert x model) for --fleet-estimator / "
                    "--fleet-online")
    args = ap.parse_args()

    if args.fleet_estimator:
        fleet_estimator_dryrun(args.fleet_estimator, args.ep)
        return
    if args.fleet_online:
        fleet_online_dryrun(args.fleet_online, args.online_batch, args.ep)
        return

    cfg = get_config(args.arch)
    ks = lm_split_points(cfg)
    k = args.split if args.split is not None else ks[len(ks) // 2]
    assert k in ks, f"split {k} not in {ks}"
    codec = {"fp16": boundary.FP16, "int8": boundary.INT8,
             "int4": boundary.INT4}[args.codec]

    mesh = make_production_mesh(multi_pod=True)
    ue, edge = pod_submesh(mesh, 0), pod_submesh(mesh, 1)
    overrides = serve_overrides(cfg)
    batch_abs = {"tokens": jax.ShapeDtypeStruct((args.batch, args.seq),
                                                jnp.int32)}
    if cfg.vision_dim:
        batch_abs["vision"] = jax.ShapeDtypeStruct(
            (args.batch, cfg.vision_tokens, cfg.vision_dim), jnp.bfloat16)

    tmpl = serve_param_template(cfg)
    results = {"arch": args.arch, "split": k, "codec": args.codec}
    with sh.use_rules(ue, overrides) as rs:
        pabs = abstract_params(cfg)
        psh = shardings_from_template(tmpl, rs)
        head = jax.jit(lambda p, b: lm_head(cfg, p, b, k),
                       in_shardings=(psh, None))
        lowered = head.lower(pabs, batch_abs)
        compiled = lowered.compile(COMPILER_OPTS)
        results["head_memory"] = str(compiled.memory_analysis())
        act_abs = jax.eval_shape(lambda p, b: lm_head(cfg, p, b, k),
                                 pabs, batch_abs)
    results["boundary_bytes"] = boundary.transmit_bytes(act_abs.shape, codec)
    with sh.use_rules(edge, overrides) as rs:
        psh = shardings_from_template(tmpl, rs)
        tail = jax.jit(lambda p, a, b: lm_tail(cfg, p, a, b, k),
                       in_shardings=(psh, None, None))
        compiled = tail.lower(pabs, act_abs, batch_abs).compile(
            COMPILER_OPTS)
        results["tail_memory"] = str(compiled.memory_analysis())
    ici_bw = 50e9
    results["boundary_transfer_ms"] = round(
        results["boundary_bytes"] / ici_bw * 1e3, 3)
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
