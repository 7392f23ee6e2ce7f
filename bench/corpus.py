"""The corpus and its base index: generation, build, and the on-disk cache.

The data is a Gaussian mixture (the arithmetic of the program's
``core/runbook.py::make_dataset``, copied so the yardstick cannot move with
the program): centres from the configuration's corpus seed, each point a
centre plus ``spread`` times a standard normal.  The base points come from
the corpus seed alone, so every ``--seed`` of a cell shares one base index;
a run's ``--seed`` draws only its traffic, from the same centres.

Building the base through the program's own insert path is the costly part
of a run (minutes at 65,536 points), so the first run of a cell in a
checkout builds it and writes its ``IndexState`` leaves under
``bench/.cache/index``; later runs restore them.  The cache key covers
everything that can change the build's arithmetic (see ``cache_key``), so
a run never restores an index built by other code or arithmetic.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

import numpy as np

from spec import BENCH_DIR, ROOT

CACHE_DIR = os.path.join(BENCH_DIR, ".cache", "index")

# parts of a configuration file that do not reach the build: the numbers
# compared and their limits, and prose
NOT_BUILT = ("limits", "about", "source", "assumed", "guarantees")

# environment that can change what the build computes
ARITHMETIC_ENV = (
    "JAX_DEFAULT_MATMUL_PRECISION", "JAX_ENABLE_X64", "XLA_FLAGS",
    "LIBTPU_INIT_ARGS", "JAX_PLATFORMS",
)


def rng(*key: int) -> np.random.Generator:
    """A generator keyed by whole numbers of any size and sign."""
    return np.random.default_rng([int(k) % 2 ** 64 for k in key])


def centres(config: dict) -> np.ndarray:
    c = config["corpus"]
    return rng(c["seed"], 0).normal(
        0.0, 1.0, size=(c["clusters"], config["index"]["dim"])
    ).astype(np.float32)


def mixture(gen: np.random.Generator, cents: np.ndarray, n: int,
            spread: float, metric: str) -> np.ndarray:
    """``n`` points of the mixture (``make_dataset``'s arithmetic)."""
    assign = gen.integers(0, len(cents), size=n)
    pts = cents[assign] + spread * gen.normal(
        0.0, 1.0, size=(n, cents.shape[1])).astype(np.float32)
    if metric == "ip":
        pts /= np.linalg.norm(pts, axis=1, keepdims=True) + 1e-9
    return pts.astype(np.float32)


def base_points(config: dict) -> np.ndarray:
    c = config["corpus"]
    return mixture(rng(c["seed"], 1), centres(config), config["live_points"],
                   c["spread"], config["index"]["metric"])


def _tree_digest(top: str) -> str:
    h = hashlib.sha256()
    for d, dirs, files in sorted(os.walk(top)):
        dirs[:] = sorted(x for x in dirs if x != "__pycache__")
        for f in sorted(files):
            if f.endswith(".pyc"):
                continue
            p = os.path.join(d, f)
            h.update(os.path.relpath(p, top).encode() + b"\0")
            with open(p, "rb") as fh:
                h.update(fh.read() + b"\0")
    return h.hexdigest()


def cache_key(config_path: str, *, src_dir: str = None,
              device_kind: str = "") -> str:
    """Hash of what the base index depends on: the configuration file (its
    sizes, corpus seed and build, every key but ``NOT_BUILT``), the
    program's source tree, this file, JAX's version and matmul precision,
    the environment that steers the compiler, and the device kind."""
    import jax

    src_dir = src_dir or os.path.join(ROOT, "src")
    h = hashlib.sha256()
    with open(config_path) as f:
        config = json.load(f)
    built = {k: v for k, v in config.items() if k not in NOT_BUILT}
    h.update(json.dumps(built, sort_keys=True).encode())
    with open(os.path.abspath(__file__), "rb") as f:
        h.update(f.read())
    h.update(_tree_digest(src_dir).encode())
    parts = {
        "jax": jax.__version__,
        "matmul_precision": str(jax.config.jax_default_matmul_precision),
        "x64": bool(jax.config.jax_enable_x64),
        "device_kind": device_kind,
        "env": {k: os.environ.get(k, "") for k in ARITHMETIC_ENV},
    }
    h.update(json.dumps(parts, sort_keys=True).encode())
    return h.hexdigest()[:24]


def build_base(config: dict, cfg, vecs: np.ndarray, log=print):
    """The base index through the program's own fill path: serial bootstrap
    inserts through ``apply(sequential=True)``, then windows through
    ``apply_segment``, under the configuration's policy."""
    import jax
    from repro.core import (apply, apply_segment, init_index_state,
                            insert_batch, pad_update_batch, plan_segments)

    b = config["build"]
    policy = config["policy"]
    n = len(vecs)
    ids = np.arange(n)
    state = init_index_state(cfg, config["max_external_id"])
    boot = b["bootstrap"]
    state, res = apply(state, cfg, insert_batch(ids[:boot], vecs[:boot]),
                       policy=policy, sequential=True)
    ok = int(np.asarray(res.ok).sum())
    w = b["window"]
    steps = [pad_update_batch(insert_batch(ids[lo:lo + w], vecs[lo:lo + w],
                                           bucket=False), w)
             for lo in range(boot, n, w)]
    t0 = time.perf_counter()
    plan = plan_segments(steps, max_t=b["segment_ops"])
    for i, seg in enumerate(plan.segments):
        state, res = apply_segment(state, cfg, seg.ops, policy=policy)
        ok += int(np.asarray(res.ok).sum())
        log(f"build segment {i + 1}/{len(plan.segments)}: inserted={ok} "
            f"elapsed_s={time.perf_counter() - t0:.1f}")
    if ok != n or int(state.graph.n_active) != n:
        raise RuntimeError(f"base build applied {ok} of {n} inserts")
    jax.block_until_ready(state)
    return state


def save_state(state, path: str) -> None:
    """Write the state's leaves as numpy files.  A leaf whose rows past some
    point all equal its last row is stored up to that point, with the last
    row as the fill (slot tables are mostly empty at 65,536 of 2^20)."""
    import jax

    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(state)]
    tmp = path + ".tmp"
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    meta = []
    for i, a in enumerate(leaves):
        hi = len(a) if a.ndim else 0
        if a.ndim and len(a):
            same = np.all((a == a[-1]).reshape(len(a), -1), axis=1)
            differ = np.nonzero(~same)[0]
            hi = int(differ[-1]) + 1 if len(differ) else 0
            np.save(os.path.join(tmp, f"{i}.fill.npy"), a[-1])
        np.save(os.path.join(tmp, f"{i}.npy"), a[:hi] if a.ndim else a)
        meta.append({"shape": list(a.shape), "dtype": str(a.dtype),
                     "rows": hi})
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump(meta, f)
    shutil.rmtree(path, ignore_errors=True)
    os.rename(tmp, path)


def load_host(path: str) -> list:
    """The saved leaves, still on the host, as (rows, fill, meta)."""
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    out = []
    for i, m in enumerate(meta):
        rows = np.load(os.path.join(path, f"{i}.npy"))
        fill = (np.load(os.path.join(path, f"{i}.fill.npy"))
                if m["shape"] else None)
        out.append((rows, fill, m))
    return out


def to_device(saved: list, like):
    """An ``IndexState`` on the default device from ``load_host``'s leaves;
    ``like`` is the state's abstract shape (shapes and dtypes must match)."""
    import jax
    import jax.numpy as jnp

    treedef = jax.tree_util.tree_structure(like)
    want = jax.tree_util.tree_leaves(like)
    if len(want) != len(saved):
        raise ValueError("cached index has another structure")
    leaves = []
    for (rows, fill, m), w in zip(saved, want):
        if tuple(m["shape"]) != tuple(w.shape) or m["dtype"] != str(w.dtype):
            raise ValueError(f"cached leaf {m} does not match {w}")
        if not m["shape"]:
            leaves.append(jnp.asarray(rows))
            continue
        tail = jnp.broadcast_to(jnp.asarray(fill),
                                (m["shape"][0] - m["rows"],) + fill.shape)
        leaves.append(jnp.concatenate([jnp.asarray(rows), tail]))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def restore_or_build(cell, cfg, device_kind: str, log=print):
    """``(host_leaves, built_s)``: the cell's base index, from the cache or
    built and written to it now (``built_s`` is None on a restore)."""
    key = cache_key(cell.config_path, device_kind=device_kind)
    path = os.path.join(CACHE_DIR, f"{cell.config_name}-{key}")
    if os.path.isfile(os.path.join(path, "meta.json")):
        log(f"base index: restored from {os.path.relpath(path, ROOT)}")
        return load_host(path), None
    t0 = time.perf_counter()
    state = build_base(cell.config, cfg, base_points(cell.config), log=log)
    built_s = time.perf_counter() - t0
    save_state(state, path)
    del state
    log(f"base index: built in {built_s:.1f} s, saved to "
        f"{os.path.relpath(path, ROOT)}")
    return load_host(path), built_s
