"""A configuration, a mix and a per-layer metric are found by the names in
BENCHMARK.json: a new one is new files plus new entries, and no file that
is already there changes."""
import hashlib
import json
import os

import harness
import spec
from conftest import TINY_CONFIG, make_root


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[p] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_config_mix_and_metric_are_files_found_by_name(tmp_path):
    root = make_root(tmp_path)
    before = _digests(root)
    # a new configuration, a new mix and a new metric, as files of their own
    with open(os.path.join(root, "bench", "configs", "dummy.json"), "w") as f:
        json.dump(dict(TINY_CONFIG, name="dummy"), f)
    with open(os.path.join(root, "bench", "traffic", "burst.json"), "w") as f:
        json.dump({"step": [{"op": "update", "inserts": 16,
                             "deletes": 16}]}, f)
    with open(os.path.join(root, "bench", "metrics", "dummy.ops.py"),
              "w") as f:
        f.write("def read(run):\n    return float(len(run.updates))\n")
    # ... and new entries in BENCHMARK.json
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "dummy", "source": "tests",
                             "file": "bench/configs/dummy.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({"name": "dummy.burst", "config": "dummy",
                               "traffic": "burst", "chips": 1,
                               "why": "tests"})
    bench["per_layer"].append({"name": "dummy.ops", "unit": "calls",
                               "better": "lower", "source": "host_clock",
                               "layer": "op stream",
                               "moves": "update_ops_per_s",
                               "workloads": ["dummy.burst"]})
    with open(path, "w") as f:
        json.dump(bench, f)

    cell = spec.load_cell("dummy.burst", root)
    assert cell.config["name"] == "dummy"
    assert cell.mix["step"][0]["deletes"] == 16
    assert [m["name"] for m in cell.per_layer] == ["dummy.ops"]
    assert {m["name"] for m in cell.end_to_end} == {"setup_s"}
    run = harness.Run(cell=cell, cfg=None, peaks={}, updates=[{}, {}])
    assert harness.per_layer(run, root) == {
        "dummy.ops": {"value": 2.0, "unit": "calls"}}
    # nothing that was there before changed
    after = _digests(root)
    assert all(after[p] == h for p, h in before.items())
