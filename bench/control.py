"""Readings the limits of ``correct`` are set from, for one cell, in one
process on the chip:

  python3 bench/control.py --workload <name> --seeds 1 2 ... --control 3

For each seed of ``--seeds``: the cell's set-up, a short closed-loop window
of ``--seconds``, and the compared numbers of the sampled calls against the
reference (the program's readings: the lower end of each limit). For each
seed of ``--control``: the same numbers for the control, the step below the
precision the configuration states (its ``control`` entry): either the
program with its own lower-precision path switched on
(``program_kwargs``), or the reference itself computed at the lower
precision in the program's place (``reference_precision``). The control
sets the upper end. One JSON line per reading; the benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import types

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import bench, check, reference, traffic  # noqa: E402


def control_results(st, hz, ctl_spec):
    """What the control produced for one horizon, in the program's result
    layout."""
    cfg = st.cell.config
    if "program_kwargs" in ctl_spec:
        return st.prog.call(hz, st.params, **ctl_spec["program_kwargs"])
    raw = st.cell.ref.raw_estimates(cfg, st.params, hz.episode,
                                    ctl_spec["reference_precision"])
    out = check.reference_outputs(hz, raw, st.tgt, cfg, hz.scheduler)
    true = np.asarray(hz.episode.tp_mbps, np.float64)
    act = out["active"]
    share = None
    if hz.kind == "fleet":
        true_r = eff = true
    else:  # replay the pool for the gathered labels and shares
        tr = reference.pool(hz.schedule, hz.capacity,
                            np.clip(raw.astype(np.float64), *check.TP_CLIP),
                            true, hz.cell, cfg["controller"], st.tgt["table"],
                            hz.scheduler, hz.n_cells)
        sid = np.clip(tr.sid, 0, true.shape[0] - 1)
        true_r = np.where(act, true[sid, tr.age], 0.0)
        eff = true_r
        if hz.scheduler is not None:
            share = np.where(act, tr.share, 0.0)
            eff = reference.prb_scaled(true_r, share)
    l = out["splits"] if act is None else np.where(act, out["splits"], 0)
    d, p, e = reference.split_metrics(st.tgt, l, eff)
    return types.SimpleNamespace(est_tp=out["est"], splits=out["splits"],
                                 active=act, prb_share=share, true_tp=true_r,
                                 delay_s=d, privacy=p, energy_j=e)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal at the files' tiny sizes on any backend")
    args = ap.parse_args(argv)
    for seed in dict.fromkeys(args.seeds + args.control):
        st = bench.setup(args.workload, seed, tiny=args.tiny,
                         require_chip=not args.tiny)
        cfg = st.cell.config
        if seed in args.seeds:
            win = bench.window(st, args.seconds,
                               keep_rng=traffic.seed_rng(seed, 7))
            nums, _ = bench.run_checks(st, win)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "side": "program", "calls": win.calls,
                              **nums}), flush=True)
        if seed in args.control:
            rows = [check.numbers(hz, control_results(st, hz, cfg["control"]),
                                  bench.expectations(st, hz), st.tgt, cfg,
                                  hz.scheduler, st.cell.target,
                                  st.target_params) for hz in st.horizons]
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "side": "control", **check.worst(rows)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
