"""Compile the chip path for a TPU v5e here, with no chip attached.

Interpret mode runs kernel bodies as plain XLA and accepts what Mosaic,
the TPU kernel compiler, refuses: block shapes off the (8, 128) tiling,
unaligned DMAs, value-level gathers, too much VMEM.  These tests lower the
kernels of the main path at deployment widths (dim 128, R 64, n_cap 2^20)
with ``interpret=False`` against a described ``v5e:2x2`` topology, plus one
whole jitted ``batched_greedy_search`` on the pallas backend and the two
batched update programs, so such a refusal fails here instead of on the
chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.ann import high_recall
from repro.core import backend as backend_mod
from repro.core import batched_greedy_search, init_state
from repro.core.batched import insert_many_batched, ip_delete_many_batched
from repro.kernels.gather_distance import (
    gather_distance,
    gather_distance_batched,
)
from repro.kernels.topk_score import topk_score

N_CAP = 2 ** 20
DIM = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    # the kernel must be in the program as a Mosaic custom call
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("k", [64, 32])
def test_gather_distance_compiles(one_chip, k):
    _compile(
        lambda i, q, v, n: gather_distance(i, q, v, n, interpret=False),
        _spec(one_chip, (k,), jnp.int32), _spec(one_chip, (DIM,)),
        _spec(one_chip, (N_CAP, DIM)), _spec(one_chip, (N_CAP,)),
    )


@pytest.mark.parametrize("b", [64, 1024])
def test_gather_distance_batched_compiles(one_chip, b):
    _compile(
        lambda i, q, v, n: gather_distance_batched(i, q, v, n,
                                                   interpret=False),
        _spec(one_chip, (b, 64), jnp.int32), _spec(one_chip, (b, DIM)),
        _spec(one_chip, (N_CAP, DIM)), _spec(one_chip, (N_CAP,)),
    )


def test_topk_score_compiles(one_chip):
    _compile(
        lambda q, v, n, b: topk_score(q, v, n, b, k=10, interpret=False),
        _spec(one_chip, (128, DIM)), _spec(one_chip, (N_CAP, DIM)),
        _spec(one_chip, (N_CAP,)), _spec(one_chip, (N_CAP,)),
    )


def test_batched_greedy_search_compiles(one_chip, monkeypatch):
    """The whole per-hop search program on the pallas backend: the chip
    path of search, insert and in-place delete phase 1."""
    monkeypatch.setattr(backend_mod.PallasBackend, "interpret", False)
    cfg = high_recall(DIM, N_CAP, backend="pallas")
    graph = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: init_state(cfg)),
    )
    b = 64
    compiled = batched_greedy_search.lower(
        graph, cfg, _spec(one_chip, (b, DIM)), k=10, l=cfg.l_search,
        valid=_spec(one_chip, (b,), jnp.bool_),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _adjacency_copies(text, cfg):
    """Names of the computations that copy the whole (n_cap, r) adjacency."""
    where, comp = [], None
    pat = re.compile(rf"= s32\[{cfg.n_cap},{cfg.r}\]\S* copy\(")
    for line in text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split()[0]
        if pat.search(line):
            where.append(comp)
    return where


@pytest.mark.parametrize("kind", ["insert", "delete"])
def test_batched_update_compiles_without_adjacency_copies(one_chip,
                                                          monkeypatch, kind):
    """The batched insert and in-place delete programs at deployment size
    (512 lanes).  On TPU the 256 MiB adjacency may be converted between
    layouts once on entry and once on exit, never inside the per-op loops
    (a copy per op or per edge costs more than the op)."""
    monkeypatch.setattr(backend_mod.PallasBackend, "interpret", False)
    cfg = high_recall(DIM, N_CAP, backend="pallas")
    graph = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: init_state(cfg)),
    )
    b = 512
    if kind == "insert":
        lowered = insert_many_batched.lower(
            graph, cfg, _spec(one_chip, (b, DIM)),
            _spec(one_chip, (b,), jnp.bool_),
        )
    else:
        lowered = ip_delete_many_batched.lower(
            graph, cfg, _spec(one_chip, (b,), jnp.int32)
        )
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text
    copies = _adjacency_copies(text, cfg)
    assert all(c == "ENTRY" for c in copies), copies
