"""Compile each cell's window programs for a described TPU v5e, with no
chip attached: ``apply_segment`` at the cell's batch shape and the query
program at B 32, for both configurations (R 64 / L 128 and R 32 / L 64,
n_cap 2^20, dim 128), on the compiled Pallas path.  A Mosaic or XLA
refusal then fails here instead of on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import spec
from harness import update_batch

from repro.core import apply_segment, init_index_state
from repro.core import backend as backend_mod
from repro.core.search import search_batch

CELLS = {
    "gmm128-hr.churn": ("gmm128-hr", "churn"),
    "gmm128-lr.search": ("gmm128-lr", "search"),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _load(name):
    with open(os.path.join(spec.BENCH_DIR, "configs", name + ".json")) as f:
        config = json.load(f)
    cfg = dataclasses.replace(spec.ann_config(config), backend="pallas")
    return config, cfg


def _mix(name):
    with open(os.path.join(spec.BENCH_DIR, "traffic", name + ".json")) as f:
        return json.load(f)


def _state_spec(sharding, cfg, max_ext):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(lambda: init_index_state(cfg, max_ext)))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_window_programs_compile(one_chip, monkeypatch, cell):
    monkeypatch.setattr(backend_mod.PallasBackend, "interpret", False)
    config_name, mix_name = CELLS[cell]
    config, cfg = _load(config_name)
    state = _state_spec(one_chip, cfg, config["max_external_id"])
    for a in _mix(mix_name)["step"]:
        if a["op"] == "update":
            ops, split = update_batch(
                np.arange(a["inserts"]),
                np.zeros((a["inserts"], cfg.dim), np.float32),
                np.arange(a["deletes"]), cfg.dim)
            ops = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=one_chip), ops)
            text = apply_segment.lower(
                state, cfg, ops, policy=config["policy"], split=split,
            ).compile().as_text()
        else:
            q = jax.ShapeDtypeStruct((a["batch"], cfg.dim), jnp.float32,
                                     sharding=one_chip)
            text = jax.jit(
                lambda g, q: search_batch(g, cfg, q, k=a["k"],
                                          l=cfg.l_search)
            ).lower(state.graph, q).compile().as_text()
        assert "tpu_custom_call" in text
