"""``chip_smoke.py`` off the chip, and the shared compile-cache helper.

The smoke script must refuse to report success anywhere but on a TPU (and
when the package is missing next to it), and its phases must hold their
own checks at a tiny size here, with the Pallas kernels in interpret mode.
"""
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import ann

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")
OK_LINE = '"ok": true'

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def _run(args, cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full = {k: v for k, v in full.items() if v is not None}
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=full, capture_output=True,
        text=True, timeout=300,
    )


def test_chip_smoke_refuses_without_tpu():
    out = _run([SCRIPT], REPO)
    assert out.returncode != 0
    assert "no TPU" in out.stdout
    assert OK_LINE not in out.stdout


def test_chip_smoke_refuses_alone(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(["chip_smoke.py"], tmp_path)
    assert out.returncode != 0
    assert OK_LINE not in out.stdout


def test_chip_smoke_single_path_tiny(capsys):
    """Fill, churn, search and every check of the one-chip path on the
    pallas engine in interpret mode, at a CPU size."""
    cfg = ann.test_scale(16, 2048, backend="pallas")
    r = chip_smoke.run_single(jax, chip_smoke.Clock(jax), cfg, 1024, 0,
                              n_queries=64, boot=32, window=64)
    assert r >= chip_smoke.RECALL_FLOOR
    out = capsys.readouterr().out
    for phase in ("data", "fill", "churn", "search", "exact_scan"):
        assert f"phase {phase}:" in out
    assert "deleted ids returned = 0" in out


SHARDED_PROBE = textwrap.dedent("""
    import sys
    import jax
    from repro.launch.mesh import shard_mesh
    devices = shard_mesh(4).devices.tolist()
    sys.path.insert(0, sys.argv[1])
    import chip_smoke
    from repro.configs import ann
    cfg = ann.test_scale(16, 1024, backend="pallas")
    chip_smoke.run_sharded(jax, chip_smoke.Clock(jax), cfg, 256, 0,
                           devices=devices, n_queries=32, boot=16, window=32,
                           min_bytes=0)
""")


def test_chip_smoke_sharded_path_tiny():
    """The ``--chips 4`` path on four host devices: fresh-policy churn with
    ``consolidate_sharded``, both partitions, placement checks."""
    out = _run(["-c", SHARDED_PROBE, REPO], REPO,
               PYTHONPATH=os.path.join(REPO, "src"))
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "FAIL" not in out.stdout
    assert "n_active per shard = [256, 256, 256, 256]" in out.stdout
    assert "consolidated shards = [0, 1, 2, 3]" in out.stdout
    assert "partitions identical top-k: True" in out.stdout
    assert "every leaf on 4 distinct devices" in out.stdout


def test_serve_shards_on_host_devices():
    """``serve --shards`` builds its mesh from host devices on the CPU."""
    out = _run(["-m", "repro.launch.serve", "--ticks", "2", "--rate", "4",
                "--queries", "4", "--dim", "16", "--shards", "2"], REPO,
               PYTHONPATH=os.path.join(REPO, "src"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "shards=2" in out.stdout


CACHE_PROBE = textwrap.dedent("""
    import os, sys
    import jax, jax.numpy as jnp
    import repro.launch.compile_cache as cc
    cc.DEFAULT_DIR = sys.argv[1]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(cc.enable_compile_cache())
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(5)).block_until_ready()
""")


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_location(tmp_path, env_set):
    """With JAX_COMPILATION_CACHE_DIR set, entries land only there; unset,
    only under the helper's fixed directory."""
    env_dir, fixed_dir = tmp_path / "env", tmp_path / "fixed"
    env = {
        "PYTHONPATH": os.path.join(REPO, "src"),
        "JAX_COMPILATION_CACHE_DIR": str(env_dir) if env_set else None,
    }
    out = _run(["-c", CACHE_PROBE, str(fixed_dir)], REPO, **env)
    assert out.returncode == 0, out.stderr[-2000:]
    used, unused = (env_dir, fixed_dir) if env_set else (fixed_dir, env_dir)
    assert out.stdout.strip() == str(used)
    assert used.is_dir() and any(used.iterdir())
    assert not unused.exists()


def test_compile_cache_default_is_in_checkout():
    from repro.launch.compile_cache import DEFAULT_DIR

    assert DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
