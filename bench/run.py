#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload gmm128-hr.churn --seed 7 \\
        --seconds 40 --trace 0

Set-up (counted in ``setup_s``): the JAX compile cache at
``bench/.cache/jax`` (or ``JAX_COMPILATION_CACHE_DIR``), the cell's base
index restored from ``bench/.cache/index`` (built and written there by the
first run in a checkout), and a warm-up of the cell's own shapes.  Then the
window, then the comparison with the plain reference.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (end-to-end with ``--trace 0``, per-layer with
``--trace 1``), ``device`` and, last, ``checks``: each number compared
beside its limit.

It runs only on TPU: with no TPU, or fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# the TPU runtime's logs stay inside the checkout
os.environ.setdefault("TPU_LOG_DIR", os.path.join(HERE, ".cache", "tpu_logs"))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import spec  # noqa: E402


def enable_compile_cache(jax) -> str:
    """The persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set (JAX reads it itself), else a fixed path inside the checkout.
    Every program is cached, however quick its compile."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = os.path.join(HERE, ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: str = spec.ROOT, platform: str = "tpu",
         control: bool = False) -> int:
    args = parse(argv)
    if not os.path.isdir(os.path.join(os.path.dirname(HERE), "src",
                                      "repro")):
        print("the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    cell = spec.load_cell(args.workload, root)

    import jax

    enable_compile_cache(jax)
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell.chips:
        print(f"needs {cell.chips} {platform} device(s); JAX found "
              f"{len(devices)} {devices[0].platform!r}", file=sys.stderr)
        return 3
    import harness

    result = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        devices=devices, t_start=T_START, root=root, control=control)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
