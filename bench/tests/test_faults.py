"""Drive a whole run with the timed path broken underneath, skipping only
the look for a chip, and see ``correct`` come out false: a step that
returns its state unchanged, half of a batch left out, an answer altered
where it is produced, a search cut short.  (One chip: there is no exchange
between chips.)"""
import copy
import json
import os

import pytest

import faults
import run as bench_run
import spec
from conftest import make_root

# caught at the tiny size by the ids, distances and update outcomes
TINY = ("state_unchanged", "half_the_updates", "half_the_queries",
        "ids_altered", "dists_altered")


def _run(root, capsys):
    argv = ["--workload", "tiny.mix", "--seed", "77", "--seconds", "3"]
    assert bench_run.main(argv, root=root, platform="cpu") == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("fault", TINY)
def test_fault_is_not_correct(tiny_root, capsys, monkeypatch, fault):
    entry, fn = faults.FAULTS[fault]
    monkeypatch.setattr(faults.core, entry, fn)
    res = _run(tiny_root, capsys)
    assert res["correct"] is False, res["checks"]


def test_capped_hop_loop_fails_the_low_recall_limits(tmp_path, capsys,
                                                     monkeypatch):
    """A search that stops after a few hops returns live ids with their
    true distances, so only ``recall_miss`` can catch it: under
    ``gmm128-lr``'s own limits, widths and corpus, cut to 8,192 points."""
    with open(os.path.join(spec.BENCH_DIR, "configs", "gmm128-lr.json")) as f:
        config = json.load(f)
    config = copy.deepcopy(config)
    config.update(live_points=8192, max_external_id=32768,
                  build={"bootstrap": 64, "window": 256, "segment_ops": 4})
    config["index"]["n_cap"] = 16384
    root = make_root(tmp_path, config=config)
    assert _run(root, capsys)["correct"] is True
    entry, fn = faults.FAULTS["hop_cap"]
    monkeypatch.setattr(faults.core, entry, fn)
    checks = _run(root, capsys)["checks"]
    assert checks["recall_miss"]["value"] > checks["recall_miss"]["limit"]
    assert all(checks[n]["value"] <= checks[n]["limit"]
               for n in ("dist_err", "bad_ids", "failed_ops"))
