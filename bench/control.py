#!/usr/bin/env python3
"""The control, and planted faults: readings for the limits.

    python3 bench/control.py --workload gmm128-hr.churn \\
        --seconds 51 --seeds 11 12 13 [--fault hop_cap]

For each seed, in one process, it sets up the cell as a run does and drives
the window through the program (so the live set moves as in a run).  Then
the control answers every query of the window with the exact scan computed
in bfloat16, the precision below the configuration's float32, and those
answers are compared as a run compares the program's.  With ``--fault``,
the program itself answers, with one of ``faults.FAULTS`` planted in its
timed path.  It prints one JSON line per seed with the numbers compared;
``correct`` has to come out false.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import faults  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402


def main(argv=None, *, root: str = spec.ROOT, platform: str = "tpu") -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, root)

    import jax

    run.enable_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell.chips:
        print(f"needs {cell.chips} {platform} device(s)", file=sys.stderr)
        return 3
    import harness

    if args.fault:
        faults.plant(args.fault)
    for seed in args.seeds:
        res = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, devices=devices,
                               t_start=time.perf_counter(), root=root,
                               control=not args.fault)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
