"""Compile the chip path for a TPU v5e here, with no chip attached.

Interpret mode runs kernel bodies as plain XLA and accepts what Mosaic,
the TPU kernel compiler, refuses: block shapes off the (8, 128) tiling,
unaligned DMAs, value-level gathers, too much VMEM.  These tests lower the
kernels of the main path at deployment widths (R 64; dim 128 and 100 at
n_cap 2^20: one and one padded 128-lane row per vector) with
``interpret=False`` against a described ``v5e:2x2`` topology, plus one
whole jitted ``batched_greedy_search`` on the pallas backend and the two
batched update programs, so such a refusal fails here instead of on the
chip.  At dim 768 (n_cap 2^21, six rows a vector) the backend takes XLA's
row gather instead of the kernel, and the same programs compile.  The
update programs donate their state, as ``apply_segment`` does, and must
never copy the vector table (6.44 GB at 768-d).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import dataclasses
import itertools
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.ann import high_recall
from repro.core import backend as backend_mod
from repro.core import batched_greedy_search, init_state
from repro.core.batched import insert_many_batched, ip_delete_many_batched
from repro.core.types import row_blocks
from repro.kernels.gather_distance import (
    gather_distance,
    gather_distance_batched,
)
from repro.kernels.topk_score import topk_score

N_CAP = 2 ** 20
DIM = 128
# (dim, n_cap): one row per vector, a padded row, six rows (Wikipedia-Cohere)
WIDTHS = [(128, N_CAP), (768, 2 ** 21), (100, N_CAP)]
WIDTH_IDS = ["dim128", "dim768", "dim100"]
# the widths the kernels take: one table row a vector
KERNEL_WIDTHS = [(128, N_CAP), (100, N_CAP)]
KERNEL_WIDTH_IDS = ["dim128", "dim100"]


def _table(dim, n_cap):
    return (n_cap * row_blocks(dim), 128)


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


@pytest.mark.parametrize("dim,n_cap", KERNEL_WIDTHS, ids=KERNEL_WIDTH_IDS)
@pytest.mark.parametrize("k", [64, 32, 1])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_gather_distance_compiles(one_chip, k, dim, n_cap, metric):
    # the query comes zero-padded to the table's row (core/backend.py)
    _compile(
        lambda i, q, v, n: gather_distance(i, q, v, n, metric=metric,
                                           interpret=False),
        _spec(one_chip, (k,), jnp.int32), _spec(one_chip, (128,)),
        _spec(one_chip, _table(dim, n_cap)), _spec(one_chip, (n_cap,)),
    )


@pytest.mark.parametrize("dim,n_cap", KERNEL_WIDTHS, ids=KERNEL_WIDTH_IDS)
@pytest.mark.parametrize("b", [32, 64, 128, 1024])
def test_gather_distance_batched_compiles(one_chip, b, dim, n_cap):
    # B 32: a query batch; B 128: the update programs' lanes.  K 64 and 32:
    # a hop's neighbour tile at R 64 and R 32; K 1: the entry point
    for metric, k in itertools.product(("l2", "ip"), (64, 32, 1)):
        compiled = _compile(
            lambda i, q, v, n: gather_distance_batched(i, q, v, n,
                                                       metric=metric,
                                                       interpret=False),
            _spec(one_chip, (b, k), jnp.int32), _spec(one_chip, (b, 128)),
            _spec(one_chip, _table(dim, n_cap)), _spec(one_chip, (n_cap,)),
        )
        assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


def test_topk_score_compiles(one_chip):
    _compile(
        lambda q, v, n, b: topk_score(q, v, n, b, k=10, interpret=False),
        _spec(one_chip, (128, DIM)), _spec(one_chip, (N_CAP, DIM)),
        _spec(one_chip, (N_CAP,)), _spec(one_chip, (N_CAP,)),
    )


def _deployment(dim, n_cap):
    """The high-recall regime on the pallas backend; at 768-d the
    Wikipedia-Cohere deployment's inner product and frozen origin start."""
    cfg = high_recall(dim, n_cap, metric="ip" if dim == 768 else "l2",
                      backend="pallas")
    return dataclasses.replace(cfg, origin_start=dim == 768)


def _graph(one_chip, cfg):
    graph = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda: init_state(cfg)),
    )
    assert graph.vectors.shape == _table(cfg.dim, cfg.n_cap)
    return graph


def _table_copies(text, cfg):
    """Instructions that copy or transpose the vector table, or make an
    array of the table's plain ``(n_cap, dim)`` shape (a relayout of it)."""
    rows, lanes = _table(cfg.dim, cfg.n_cap)
    copy = re.compile(rf"= f32\[{rows},{lanes}\]\S* (copy|transpose)\(")
    plain = re.compile(rf"= f32\[{cfg.n_cap},{cfg.dim}\]")
    return [line for line in text.splitlines()
            if copy.search(line) or (cfg.dim != lanes and plain.search(line))]


@pytest.mark.parametrize("dim,n_cap", WIDTHS, ids=WIDTH_IDS)
def test_batched_greedy_search_compiles(one_chip, monkeypatch, dim, n_cap):
    """The whole per-hop search program on the pallas backend: the chip
    path of search, insert and in-place delete phase 1."""
    monkeypatch.setattr(backend_mod.PallasBackend, "interpret", False)
    cfg = _deployment(dim, n_cap)
    b = 64
    compiled = batched_greedy_search.lower(
        _graph(one_chip, cfg), cfg, _spec(one_chip, (b, dim)), k=10,
        l=cfg.l_search, valid=_spec(one_chip, (b,), jnp.bool_),
    ).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (row_blocks(dim) == 1)
    assert not _table_copies(text, cfg)


@pytest.mark.parametrize("b", [64, 1024])
@pytest.mark.parametrize("metric", ["l2", "ip"])
def test_multi_row_gather_compiles_without_table_copies(one_chip, monkeypatch,
                                                        b, metric):
    """At 768-d the pallas backend's hop gather is XLA's row gather: no
    kernel, no copy of the 6.44 GB table."""
    monkeypatch.setattr(backend_mod.PallasBackend, "interpret", False)
    cfg = high_recall(768, 2 ** 21, metric=metric, backend="pallas")
    be = backend_mod.get_backend("pallas")
    compiled = jax.jit(
        lambda g, q, i: be.dists_to_ids_batched(g, cfg, q, i)
    ).lower(_graph(one_chip, cfg), _spec(one_chip, (b, 768)),
            _spec(one_chip, (b, cfg.r), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert not _table_copies(text, cfg)
    # the gathered (b, r, 768) rows and their products: a few times a
    # tile's rows, far below one table
    gathered = 4 * b * cfg.r * 768
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * gathered


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


@pytest.mark.parametrize("dim,n_cap", WIDTHS, ids=WIDTH_IDS)
@pytest.mark.parametrize("kind", ["insert", "delete"])
def test_batched_update_compiles_without_adjacency_copies(
        one_chip, monkeypatch, kind, dim, n_cap):
    """The batched insert and in-place delete programs at deployment size
    (512 lanes at 128-d, a churn cell's 128 at the other widths), their
    state donated.  On TPU the adjacency (256 MiB at 2^20) may be converted
    between layouts once on entry and once on exit, never inside the
    per-op loops (a copy per op or per edge costs more than the op); the
    vector table is never copied, and at 768-d the program's temporaries
    stay below one table."""
    monkeypatch.setattr(backend_mod.PallasBackend, "interpret", False)
    cfg = _deployment(dim, n_cap)
    graph = _graph(one_chip, cfg)
    b = 512 if dim == DIM else 128
    if kind == "insert":
        lowered = jax.jit(
            lambda g, xs, ok: insert_many_batched(g, cfg, xs, ok),
            donate_argnums=0,
        ).lower(graph, _spec(one_chip, (b, dim)),
                _spec(one_chip, (b,), jnp.bool_))
    else:
        lowered = jax.jit(
            lambda g, ps: ip_delete_many_batched(g, cfg, ps),
            donate_argnums=0,
        ).lower(graph, _spec(one_chip, (b,), jnp.int32))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (row_blocks(dim) == 1)
    copies = _adjacency_copies(text, cfg)
    assert all(c == "ENTRY" for c in copies), copies
    assert not _table_copies(text, cfg)
    if dim == 768:
        table_bytes = 4 * cfg.n_cap * dim
        assert compiled.memory_analysis().temp_size_in_bytes < table_bytes
