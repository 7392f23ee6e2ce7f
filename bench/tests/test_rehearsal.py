"""A whole run on the CPU at tiny size: the last line is well formed, the
second run restores the base index, and without a TPU the benchmark fails
and prints no result."""
import json
import subprocess
import sys

import pytest

import run as bench_run
from conftest import BENCH

TOP = {"correct", "attempted", "failed", "metrics", "device", "checks"}


def _last_line(capsys):
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_untraced_run_prints_end_to_end_metrics(tiny_root, capsys):
    argv = ["--workload", "tiny.mix", "--seed", str(2 ** 31 + 11),
            "--seconds", "2", "--trace", "0"]
    assert bench_run.main(argv, root=tiny_root, platform="cpu") == 0
    res, err = _last_line(capsys)
    assert TOP <= set(res) and list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"update_ops_per_s", "queries_per_s",
                                   "query_p95_ms", "recall_at_10.churn",
                                   "recall_at_10.search",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert "compiles inside 0" in err
    assert err.strip().splitlines()[-1].startswith("check failed_ops:")
    # a second run restores what the first built
    assert bench_run.main(argv, root=tiny_root, platform="cpu") == 0
    assert "restored from" in capsys.readouterr().err


def test_traced_run_prints_per_layer_metrics(tiny_root, capsys):
    argv = ["--workload", "tiny.mix", "--seed", "-3", "--seconds", "1",
            "--trace", "1"]
    assert bench_run.main(argv, root=tiny_root, platform="cpu") == 0
    res, _ = _last_line(capsys)
    assert res["correct"] is True
    assert {"update.comps_per_delete", "update.comps_per_insert",
            "search.hops_per_query", "api.call_ms.update",
            "api.call_ms.query"} <= set(res["metrics"])
    # the CPU has no device plane: no share of a roofline or of idle time
    assert not any("roofline" in n or "idle" in n for n in res["metrics"])
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_tpu_fails_without_a_result():
    p = subprocess.run(
        [sys.executable, f"{BENCH}/run.py", "--workload", "gmm128-hr.churn",
         "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "tpu" in p.stderr


@pytest.mark.parametrize("missing", ["src"])
def test_a_checkout_without_the_program_fails(tmp_path, missing):
    import shutil

    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(f"{BENCH}/../BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "gmm128-hr.churn",
         "--seed", "1", "--seconds", "1"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0 and p.stdout.strip() == ""
