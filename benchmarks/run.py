# One function per paper table. Print ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import argparse
import sys
import time
import traceback

from . import common  # noqa: F401  (sets sys.path for repro)

MODULES = [
    "table1_runbooks",
    "table2_low_recall",
    "table3_ablations",
    "table4_consolidation",
    "figure1_curves",
    "figure2_static_rebuild",
    "query_throughput",
    "perf_ann",
    "backend_bench",
    "search_bench",
    "scale_bench",
    "update_bench",
    "shard_bench",
    "serve_bench",
    "recover_bench",
    "roofline",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset")
    args = ap.parse_args()
    mods = args.only.split(",") if args.only else MODULES
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for name in mods:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        t0 = time.time()
        try:
            for row in mod.run():
                print(row.csv(), flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            traceback.print_exc(file=sys.stderr)
            print(f"{name}.FAILED,0.00,{type(e).__name__}", flush=True)
        print(f"# {name} took {time.time()-t0:.1f}s", flush=True)
        import jax
        jax.clear_caches()  # 1-core box: drop compiled executables between
        # modules or the accumulated cache exhausts host RAM
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
