import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402  (XLA_FLAGS must precede every other import, incl. repro.*)
import jax

# An AOT tool on virtual host devices: it never opens an accelerator, so a
# chip on this host stays with the process that serves on it. The --all
# children run this module too and make the same choice.
jax.config.update("jax_platforms", "cpu")

import argparse
import json
import pathlib
import subprocess
import sys
import time
import traceback

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "benchmarks" / (
    "results") / "dryrun"


def run_cell(arch: str, shape: str, multi_pod: bool, remat: str,
             overrides: dict | None, grad_accum: int | None,
             calibrate: bool = True, kv_dtype: str = "bf16",
             bf16_gather: bool = False, weight_dtype: str = "bf16") -> dict:
    from repro.configs import SHAPES, cell_status, get_config
    from repro.dist import sharding as sh
    from repro.launch import roofline as rl
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import COMPILER_OPTS, build_cell, lower_cell

    import dataclasses as _dc

    from repro.launch.steps import serve_overrides

    cfg = get_config(arch)
    if kv_dtype != "bf16":
        cfg = _dc.replace(cfg, kv_dtype=kv_dtype)
    status = cell_status(arch, shape)
    if SHAPES[shape][2] in ("prefill", "decode"):
        overrides = {**serve_overrides(cfg), **(overrides or {})} or None
    rec = {"arch": arch, "shape": shape,
           "mesh": "2x16x16" if multi_pod else "16x16", "status": status,
           "remat": remat, "overrides": overrides or {},
           "kv_dtype": kv_dtype, "bf16_gather": bf16_gather,
           "weight_dtype": weight_dtype}
    if status != "ok":
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with sh.use_rules(mesh, overrides) as rs:
        cell = build_cell(cfg, shape, rs, remat=remat, grad_accum=grad_accum,
                          bf16_gather=bf16_gather, weight_dtype=weight_dtype)
        lowered = lower_cell(cell, mesh, overrides)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile(COMPILER_OPTS)
        rec["compile_s"] = round(time.time() - t1, 2)
        rec["grad_accum"] = cell.grad_accum
        mem = compiled.memory_analysis()
        rec["memory_analysis"] = str(mem)
        rec["cost_analysis"] = {
            k: v for k, v in (compiled.cost_analysis() or {}).items()
            if k in ("flops", "bytes accessed", "transcendentals")
        }
    costvec = None
    if calibrate and not multi_pod:
        # loop-corrected costs via unrolled 1x/2x-pattern compiles (pod1 only:
        # the roofline table is single-pod; pod2 proves sharding coherence)
        t2 = time.time()
        costvec = rl.calibrated_costs(cfg, shape, mesh, overrides,
                                      remat=remat, grad_accum=cell.grad_accum,
                                      bf16_gather=bf16_gather)
        rec["calibrate_s"] = round(time.time() - t2, 2)
    rec["roofline"] = rl.roofline(compiled, mesh, cfg, shape, SHAPES,
                                  cell.grad_accum, costvec=costvec)
    return rec


def cell_path(arch: str, shape: str, multi_pod: bool) -> pathlib.Path:
    mesh = "pod2" if multi_pod else "pod1"
    return RESULTS_DIR / mesh / f"{arch}__{shape}.json"


def main() -> int:
    ap = argparse.ArgumentParser(description="multi-pod AOT dry-run")
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep every (arch x shape x mesh) in subprocesses")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--grad-accum", type=int, default=None)
    ap.add_argument("--overrides", default=None,
                    help='JSON dict of sharding-rule overrides')
    ap.add_argument("--tag", default=None,
                    help="write result to a tagged filename (perf experiments)")
    ap.add_argument("--no-calibrate", action="store_true")
    ap.add_argument("--kv-dtype", default="bf16", choices=["bf16", "int8"])
    ap.add_argument("--weight-dtype", default="bf16", choices=["bf16", "int8"])
    ap.add_argument("--bf16-gather", action="store_true")
    args = ap.parse_args()

    if args.all:
        from repro.configs import SHAPES, list_archs
        failures = []
        for arch in list_archs():
            for shape in SHAPES:
                for mp in (False, True):
                    out = cell_path(arch, shape, mp)
                    if out.exists() and not args.force:
                        continue
                    cmd = [sys.executable, "-m", "repro.launch.dryrun",
                           "--arch", arch, "--shape", shape,
                           "--remat", args.remat]
                    if mp:
                        cmd.append("--multi-pod")
                    if args.force:
                        cmd.append("--force")
                    print(f"[dryrun] {arch} {shape} "
                          f"{'pod2' if mp else 'pod1'}", flush=True)
                    r = subprocess.run(cmd, env={**os.environ,
                                                 "PYTHONPATH": "src"})
                    if r.returncode:
                        failures.append((arch, shape, mp))
        print(f"[dryrun] sweep done, {len(failures)} failures: {failures}")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch and --shape required"
    overrides = json.loads(args.overrides) if args.overrides else None
    out = cell_path(args.arch, args.shape, args.multi_pod)
    if args.tag:
        out = out.with_name(out.stem + f"__{args.tag}.json")
    if out.exists() and not args.force:
        print(f"[dryrun] cached: {out}")
        return 0
    try:
        rec = run_cell(args.arch, args.shape, args.multi_pod, args.remat,
                       overrides, args.grad_accum,
                       calibrate=not args.no_calibrate,
                       kv_dtype=args.kv_dtype, bf16_gather=args.bf16_gather,
                       weight_dtype=args.weight_dtype)
    except Exception:
        rec = {"arch": args.arch, "shape": args.shape,
               "mesh": "2x16x16" if args.multi_pod else "16x16",
               "status": "error", "traceback": traceback.format_exc()}
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(rec, indent=1))
        print(rec["traceback"], file=sys.stderr)
        return 1
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=1))
    r = rec.get("roofline", {})
    print(json.dumps({k: rec.get(k) for k in
                      ("arch", "shape", "mesh", "status", "lower_s",
                       "compile_s", "grad_accum")}, indent=1))
    if r:
        print(f"  t_compute={r['t_compute_s']:.4f}s t_memory="
              f"{r['t_memory_s']:.4f}s t_collective={r['t_collective_s']:.4f}s"
              f" bottleneck={r['bottleneck']} useful={r['useful_flops_ratio']:.3f}"
              f" fits16GB={r.get('fits_16gb_hbm')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
