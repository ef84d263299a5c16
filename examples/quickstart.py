"""Quickstart: the paper's pipeline in one page.

1. Profile VGG16's 43 split points (FLOPs, bytes, privacy).
2. Build the PSO lookup table (Algorithm 1).
3. Drive the adaptive controller through a throughput collapse and watch
   the split move; run the actual split inference at both operating points.

Run: PYTHONPATH=src python examples/quickstart.py
     (--smoke: CI mode — one operating point, same end-to-end path)
"""
import argparse

import jax
import numpy as np

ap = argparse.ArgumentParser(description="paper pipeline quickstart")
ap.add_argument("--smoke", action="store_true",
                help="CI mode: run a single operating point")
ARGS = ap.parse_args()

from repro.core import boundary
from repro.core.controller import AdaptiveSplitController
from repro.core.energy import EDGE_A40X2, UE_VM_2CORE
from repro.core.objective import Constraints, Weights
from repro.core.pso import pso_vectorized
from repro.core.splitting import vgg_head, vgg_tail
from repro.launch.cache import enable_compile_cache
from repro.models.vgg import FULL, REDUCED, init_vgg, vgg_split_profile

enable_compile_cache()

# 1. profile -------------------------------------------------------------
profile = vgg_split_profile(FULL)
print(f"profile: {profile.n_splits} split points, "
      f"{profile.total_flops/1e9:.1f} GFLOPs total")

# 2. PSO lookup table (Algorithm 1) --------------------------------------
table = pso_vectorized(
    profile, UE_VM_2CORE, EDGE_A40X2,
    Weights(w_delay=1.0, w_privacy=0.15, w_energy=0.1),
    Constraints(rho_max=0.92, tau_max_s=6.0, e_max_j=40.0),
    tp_max_mbps=130)
print("lookup table (TP Mbps -> split):",
      {tp: int(table.table[tp]) + 1 for tp in (5, 10, 20, 40, 80, 130)})

# 3. adaptive control through a throughput collapse ----------------------
ctl = AdaptiveSplitController(table)
for tp in [120, 118, 95, 60, 22, 9, 8, 7, 9, 8]:
    l = ctl.update(tp)
    print(f"  estimator reports {tp:4d} Mbps -> run layers 1..{l+1} on UE")

# actual split inference on the reduced (CPU-sized) VGG ------------------
params = init_vgg(REDUCED, jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1),
                      (2, REDUCED.image_size, REDUCED.image_size, 3))
for tp in ((130,) if ARGS.smoke else (130, 8)):
    l = table.query(tp)
    act = vgg_head(REDUCED, params, x, l)  # runs on the UE
    act = boundary.roundtrip(act, boundary.INT8)  # 4x smaller uplink
    out = vgg_tail(REDUCED, params, act, l)  # runs on the edge
    print(f"TP={tp:3d} Mbps: split at {l+1}, boundary "
          f"{np.prod(act.shape)} els, probs sum={float(out.sum()):.3f}")
print("done.")
