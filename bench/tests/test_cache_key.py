"""The base-index cache key moves with what the build depends on, and a
saved state comes back leaf for leaf."""
import json
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np

import corpus
import spec


def _key(tmp_path, **kw):
    return corpus.cache_key(os.path.join(spec.BENCH_DIR, "configs",
                                         "gmm128-hr.json"), **kw)


def test_key_moves_with_the_source_tree(tmp_path):
    src = tmp_path / "src"
    shutil.copytree(os.path.join(spec.ROOT, "src"), src,
                    ignore=shutil.ignore_patterns("__pycache__"))
    a = _key(tmp_path, src_dir=str(src))
    assert a == _key(tmp_path, src_dir=str(src))
    with open(src / "repro" / "core" / "insert.py", "a") as f:
        f.write("\n# changed\n")
    assert _key(tmp_path, src_dir=str(src)) != a


def test_key_moves_with_precision_env_device_and_config(tmp_path,
                                                        monkeypatch):
    a = _key(tmp_path)
    with jax.default_matmul_precision("highest"):
        assert _key(tmp_path) != a
    assert _key(tmp_path) == a
    monkeypatch.setenv("XLA_FLAGS", "--xla_some_flag=1")
    assert _key(tmp_path) != a
    monkeypatch.delenv("XLA_FLAGS")
    assert _key(tmp_path, device_kind="TPU v5 lite") != a
    other = corpus.cache_key(os.path.join(spec.BENCH_DIR, "configs",
                                          "gmm128-lr.json"))
    assert other != a


def test_save_and_restore_round_trip(tmp_path):
    rows = np.zeros((64, 4), np.float32)
    rows[:5] = np.arange(20).reshape(5, 4)
    state = {"vec": jnp.asarray(rows),
             "stack": jnp.arange(63, -1, -1, dtype=jnp.int32),
             "map": jnp.full((64,), -1, jnp.int32).at[3].set(7),
             "n": jnp.int32(5)}
    corpus.save_state(state, str(tmp_path / "s"))
    saved = corpus.load_host(str(tmp_path / "s"))
    # the mostly-empty tables are stored only up to their last used row
    assert {m["rows"] for _, _, m in saved} == {5, 63, 4, 0}
    back = corpus.to_device(saved, jax.eval_shape(lambda: state))
    for k in state:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(state[k]))


def test_key_moves_with_the_build_and_not_with_the_limits(tmp_path):
    with open(os.path.join(spec.BENCH_DIR, "configs", "gmm128-lr.json")) as f:
        config = json.load(f)

    def key(cfg):
        path = tmp_path / "c.json"
        path.write_text(json.dumps(cfg))
        return corpus.cache_key(str(path))

    a = key(config)
    assert key(dict(config, limits={"bad_ids": 0}, about="other")) == a
    assert key(dict(config, live_points=4096)) != a
    assert key(dict(config, corpus=dict(config["corpus"], seed=1))) != a
    assert key(dict(config, index=dict(config["index"], r=16))) != a
