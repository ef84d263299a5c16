"""Run one cell of the benchmark once, on the chip this process finds.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the split target's lookup table, generates the cell's
horizons from the seed, makes the estimator's weights on the device,
calibrates their readout, and warms one call per horizon. The window then
calls ``simulate_fleet`` in a closed loop, cycling through the horizons,
for ``--seconds``. After it, the reference checks the calls sampled from
the window. With ``--trace 1`` the window runs under the profiler and the
result carries the cell's per-layer metrics instead of its end-to-end
ones. The last line of standard output is the result's JSON object; the
last lines of standard error are the compared numbers with their limits.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# the TPU runtime would log under a fixed path in /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from harness import bench  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = bench.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), t_start=T_START)
    except bench.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
