"""Harness tests: run on the CPU at tiny sizes, from the repository root
with ``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
import json
import os
import shutil
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import pytest  # noqa: E402

TINY_CONFIG = {
    "name": "tiny",
    "index": {"dim": 16, "n_cap": 1024, "r": 16, "l_build": 32,
              "l_search": 32, "l_delete": 32, "k_delete": 16,
              "n_copies": 3, "alpha": 1.2, "metric": "l2",
              "consolidation_threshold": 0.2},
    "policy": "ip",
    "dtype": "float32",
    "live_points": 256,
    "max_external_id": 4096,
    "corpus": {"seed": 5, "clusters": 8, "spread": 0.35},
    "build": {"bootstrap": 64, "window": 64, "segment_ops": 2},
    "limits": {"recall_miss": 0.1, "dist_err": 1e-4, "bad_ids": 0,
               "failed_ops": 0},
}

TINY_MIX = {
    "step": [
        {"op": "update", "inserts": 8, "deletes": 8},
        {"op": "search", "batches": 2, "batch": 8, "k": 10},
    ],
    "trace_steps": 1,
}


def make_root(path, config=TINY_CONFIG, mix=TINY_MIX):
    """A checkout-like root holding BENCHMARK.json with one tiny cell, the
    harness's metric readers, and the tiny config and mix as files."""
    os.makedirs(os.path.join(path, "bench", "configs"))
    os.makedirs(os.path.join(path, "bench", "traffic"))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(path, "bench", "metrics"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(BENCH, "peaks.json"),
                os.path.join(path, "bench", "peaks.json"))
    with open(os.path.join(path, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(path, "bench", "traffic", "mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "tests"}]
    bench["workloads"] = [{"name": "tiny.mix", "config": "tiny",
                           "traffic": "mix", "chips": 1, "why": "tests"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.mix"]
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return str(path)


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
