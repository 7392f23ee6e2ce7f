#!/usr/bin/env python3
"""Smoke run of the streaming index on TPU, at deployment size.

Drives the index's main path once through its public front doors, with
the paper's high-recall configuration (``configs/ann.py::high_recall``:
R 64, l 128, alpha 1.2, l2) at dim 128 and n_cap = 2^20 slots:

  fill    65,536 points: 256 serial inserts (``apply(sequential=True)``,
          the bootstrap regime), then relaxed-visibility windows of 512
          inserts through ``apply_segment`` (ip policy);
  churn   delete 10% of the fill in place and insert as many new points,
          as one mixed segment;
  search  1,024 queries at k = 10 through ``search_index``.

It checks recall@10 against an exact numpy scan over the live set (floor
0.90), that no deleted id is returned, and that the Pallas kernels ran
compiled (not interpreted).  Each phase prints its wall time and the part
of it spent lowering and compiling.  The last line of standard output is
the JSON result; any failed check exits non-zero without it.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded path only, on four chips

``--chips 4`` builds a ``ShardedIndex`` with 4 logical shards on a mesh of
the four chips, each shard the one-chip state (n_cap 2^20, 65,536 points),
runs one churn window through ``update_stream`` under the fresh policy
(whose consolidation runs each shard's Algorithm 4 on the chip that holds
it), then searches under both query partitions.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

DIM = 128
N_CAP = 2 ** 20
FILL = 65_536
BOOT = 256          # serial bootstrap inserts (2 * l_build, as StreamingIndex)
WINDOW = 512        # relaxed-visibility insert window (StreamingIndex's cap)
SEG_T = 8           # windows per compiled segment
N_QUERIES = 1024
K = 10
CHURN = 0.10
RECALL_FLOOR = 0.90

# lowering to MLIR and the XLA/Mosaic compile; tracing is left out (a
# nested jit's trace event falls inside its caller's, so sums overcount)
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class Clock:
    """Per-phase wall time, with the seconds JAX spent lowering and
    compiling inside the phase reported apart (monitoring events)."""

    def __init__(self, jax):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    @contextlib.contextmanager
    def phase(self, name, **info):
        c0, t0 = self.compile_s, time.perf_counter()
        yield info
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        extra = "".join(f" {k}={v}" for k, v in info.items())
        print(f"phase {name}: wall_s={wall:.3f} compile_s={comp:.3f} "
              f"run_s={wall - comp:.3f}{extra}", flush=True)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def exact_topk(live_ids, live_vecs, queries, k):
    """Plain numpy reference: exact squared-l2 top-k external ids."""
    x = live_vecs.astype(np.float32)
    xn = np.einsum("ij,ij->i", x, x)
    out = np.empty((len(queries), k), np.int64)
    for lo in range(0, len(queries), 128):
        q = queries[lo:lo + 128]
        d = xn[None, :] - 2.0 * (q @ x.T)
        part = np.argpartition(d, k, axis=1)[:, :k]
        order = np.argsort(np.take_along_axis(d, part, axis=1), axis=1)
        out[lo:lo + 128] = live_ids[np.take_along_axis(part, order, axis=1)]
    return out


def recall(found, truth, k):
    return float(np.mean([
        len(set(f[:k].tolist()) & set(t[:k].tolist())) / k
        for f, t in zip(found, truth)
    ]))


def check_answers(ext, dists, dead, truth, tag):
    """The result contract every path shares: shape, finiteness, no
    deleted id, recall floor.  Returns recall@k."""
    ext, dists = np.asarray(ext), np.asarray(dists)
    if ext.shape != truth.shape or dists.shape != truth.shape:
        fail(f"{tag}: result shape {ext.shape} / {dists.shape}")
    if (ext < 0).any() or not np.isfinite(dists).all():
        fail(f"{tag}: invalid ids or non-finite distances returned")
    n_dead = int(np.isin(ext, dead).sum())
    print(f"{tag}: deleted ids returned = {n_dead}", flush=True)
    if n_dead:
        fail(f"{tag}: {n_dead} deleted ids returned")
    r = recall(ext, truth, K)
    print(f"{tag}: recall@{K} = {r:.4f} (floor {RECALL_FLOOR})", flush=True)
    if r < RECALL_FLOOR:
        fail(f"{tag}: recall@{K} {r:.4f} below {RECALL_FLOOR}")
    return r


def insert_windows(ext_ids, vecs, width):
    """Fixed-width insert batches (masked tail lanes keep one shape)."""
    from repro.core import insert_batch, pad_update_batch

    return [
        pad_update_batch(insert_batch(ext_ids[lo:lo + width],
                                      vecs[lo:lo + width], bucket=False),
                         width)
        for lo in range(0, len(ext_ids), width)
    ]


def churn_windows(new_ids, new_vecs, dead_ids, width):
    """Kind-major mixed batches: ``width`` inserts then ``width`` deletes.
    Returns ``[(batch, split), ...]``."""
    from repro.core import mixed_update_batch

    return [
        mixed_update_batch(new_ids[lo:lo + width], new_vecs[lo:lo + width],
                           dead_ids[lo:lo + width], new_vecs.shape[1])
        for lo in range(0, len(new_ids), width)
    ]


def run_single(jax, clock, cfg, fill, seed, *, n_queries=N_QUERIES,
               boot=BOOT, window=WINDOW):
    """Fill, churn and search one index on the default device."""
    import jax.numpy as jnp
    from repro.core import (
        apply, apply_segment, brute_force_topk, init_index_state,
        insert_batch, make_dataset, plan_segments, search_index,
    )

    n_churn = int(fill * CHURN)
    with clock.phase("data", n=fill + n_churn, queries=n_queries):
        data, queries = make_dataset(fill + n_churn, cfg.dim, "l2",
                                     n_queries=n_queries, seed=seed)
        ids = np.arange(fill + n_churn)
        state = init_index_state(cfg, fill + n_churn)
        jax.block_until_ready(state)

    with clock.phase("fill", fill=fill) as info:
        state, res = apply(state, cfg, insert_batch(ids[:boot], data[:boot]),
                           policy="ip", sequential=True)
        ok = int(np.asarray(res.ok).sum())
        plan = plan_segments(
            insert_windows(ids[boot:fill], data[boot:fill], window),
            max_t=SEG_T,
        )
        t0 = time.perf_counter()
        for i, seg in enumerate(plan.segments):
            state, res = apply_segment(state, cfg, seg.ops, policy="ip")
            ok += int(np.asarray(res.ok).sum())
            dt = time.perf_counter() - t0
            print(f"  fill segment {i + 1}/{len(plan.segments)}: "
                  f"inserted={ok} elapsed_s={dt:.1f}", flush=True)
        info["inserted"] = ok
    if ok != fill or int(state.graph.n_active) != fill:
        fail(f"fill: {ok} of {fill} inserts applied")

    rng = np.random.default_rng(seed + 1)
    dead = np.sort(rng.choice(fill, n_churn, replace=False))
    new = ids[fill:]
    with clock.phase("churn", deletes=n_churn, inserts=n_churn) as info:
        steps = churn_windows(new, data[fill:], dead, window)
        plan = plan_segments([b for b, _ in steps],
                             splits=[s for _, s in steps], max_t=SEG_T)
        ok = 0
        t0 = time.perf_counter()
        for i, seg in enumerate(plan.segments):
            state, res = apply_segment(state, cfg, seg.ops, policy="ip",
                                       split=seg.split)
            ok += int(np.asarray(res.ok).sum())
            dt = time.perf_counter() - t0
            print(f"  churn segment {i + 1}/{len(plan.segments)}: "
                  f"applied={ok} elapsed_s={dt:.1f}", flush=True)
        info["applied"] = ok
    if ok != 2 * n_churn or int(state.graph.n_active) != fill:
        fail(f"churn: {ok} of {2 * n_churn} ops applied")

    q = jnp.asarray(queries)
    for name in ("search", "search_warm"):
        with clock.phase(name, queries=n_queries, k=K):
            ext, dists, _ = search_index(state, cfg, q, k=K)
            jax.block_until_ready((ext, dists))

    live = np.setdiff1d(ids, dead)
    with clock.phase("reference"):
        truth = exact_topk(live, data[live], queries, K)
    r = check_answers(ext, dists, dead, truth, "search")

    with clock.phase("exact_scan"):
        slots, _ = brute_force_topk(state, cfg, q, k=K)
        scan = np.asarray(state.slot2ext)[np.asarray(slots)]
    agree = recall(scan, truth, K)
    print(f"exact_scan (topk_score kernel) agreement with numpy = "
          f"{agree:.4f}", flush=True)
    return r


def balanced_ids(route, n_shards, per_shard, start=0):
    """``n_shards * per_shard`` external ids from ``start`` up, interleaved
    so that every run of ``n_shards * w`` ids owns exactly ``w`` per shard
    (the steady state of hash routing at scale; keeps each shard's compact
    lane bucket at one width)."""
    pool = np.arange(start, start + 3 * n_shards * per_shard)
    own = route(pool)
    cols = [pool[own == s][:per_shard] for s in range(n_shards)]
    if min(len(c) for c in cols) < per_shard:
        fail("not enough balanced external ids")
    return np.stack(cols, axis=1).reshape(-1)


def run_sharded(jax, clock, cfg, per_shard, seed, *, devices,
                n_queries=N_QUERIES, boot=BOOT, window=WINDOW,
                min_bytes=None):
    """Fill, churn and search a ``ShardedIndex`` with one logical shard
    per device of ``devices``, each shard at the single-chip state size.
    ``min_bytes``: device bytes in use each device must show (default:
    one shard's state bytes)."""
    from repro.core import insert_batch, make_dataset, mixed_update_batch
    from repro.core.distributed import ShardedIndex

    S = len(devices)
    mesh = jax.make_mesh((S,), ("shard",), devices=devices)
    n_churn = int(per_shard * CHURN)

    def make(sequential, max_ext):
        return ShardedIndex(cfg, mesh, policy="fresh", n_logical=S,
                            sequential=sequential, max_external_id=max_ext)

    with clock.phase("data", shards=S, per_shard=per_shard):
        # balanced_ids draws from 3x pools: fill ids, then churn ids above
        max_ext = 3 * S * (per_shard + n_churn)
        boot_idx = make(True, max_ext)
        fill_ids = balanced_ids(boot_idx.route, S, per_shard)
        new_ids = balanced_ids(boot_idx.route, S, n_churn,
                               start=3 * S * per_shard)
        ids = np.concatenate([fill_ids, new_ids])
        data, queries = make_dataset(len(ids), cfg.dim, "l2",
                                     n_queries=n_queries, seed=seed)
        row = np.zeros((max_ext,), np.int64)
        row[ids] = np.arange(len(ids))
        jax.block_until_ready(boot_idx.states)

    def vecs(e):
        return data[row[e]]

    with clock.phase("fill", fill=S * per_shard):
        # serial bootstrap per shard, then relaxed-visibility windows; the
        # stacked state moves between the two programs' indexes as is
        first = fill_ids[:S * boot]
        boot_idx.update_stream([insert_batch(first, vecs(first))])
        idx = make(False, max_ext)
        idx.states, boot_idx.states = boot_idx.states, None
        rest, w = fill_ids[S * boot:], S * window
        idx.update_stream(
            [insert_batch(rest[lo:lo + w], vecs(rest[lo:lo + w]))
             for lo in range(0, len(rest), w)],
            max_t=SEG_T,
        )
        n_active = np.asarray(idx.states.graph.n_active)
    print(f"fill: n_active per shard = {n_active.tolist()}", flush=True)
    if int(n_active.sum()) != S * per_shard:
        fail("sharded fill incomplete")

    # fill_ids is (per_shard, S) row-major by owner: delete n_churn of each
    # shard's points, interleaved the same way so every window stays
    # balanced
    rng = np.random.default_rng(seed + 1)
    by_shard = fill_ids.reshape(per_shard, S)
    dead = np.stack([
        rng.choice(by_shard[:, s], n_churn, replace=False) for s in range(S)
    ], axis=1).reshape(-1)
    with clock.phase("churn", deletes=len(dead), inserts=len(new_ids)):
        w = S * window
        idx.update_stream(
            [mixed_update_batch(new_ids[lo:lo + w], vecs(new_ids[lo:lo + w]),
                                dead[lo:lo + w], cfg.dim)[0]
             for lo in range(0, len(new_ids), w)],
            max_t=SEG_T,
        )
        pending = np.asarray(idx.states.graph.n_pending).tolist()
        done = idx.consolidate_sharded(force=True)
        jax.block_until_ready(idx.states)
    print(f"churn: tombstones before consolidation = {pending}, "
          f"consolidated shards = {done}", flush=True)
    if int(np.asarray(idx.states.graph.n_pending).sum()) != 0:
        fail("tombstones left after consolidate_sharded")

    live = np.setdiff1d(ids, dead)
    with clock.phase("reference"):
        truth = exact_topk(live, vecs(live), queries, K)
    out = {}
    for part in ("replicate", "queries"):
        for name in (f"search_{part}", f"search_{part}_warm"):
            with clock.phase(name, queries=n_queries, k=K):
                ext, _, dists, _ = idx.search(queries, k=K, l=cfg.l_search,
                                              partition=part)
        check_answers(ext, dists, dead, truth, f"search[{part}]")
        out[part] = ext
    same = bool(np.array_equal(out["replicate"], out["queries"]))
    print(f"partitions identical top-k: {same}", flush=True)
    if not same:
        fail("replicate and queries partitions disagree")

    for leaf in jax.tree.leaves(idx.states):
        where = {s.device for s in leaf.addressable_shards}
        if len(where) != S:
            fail(f"a stacked leaf sits on {len(where)} devices, not {S}")
    print(f"stacked leaves: every leaf on {S} distinct devices", flush=True)
    if min_bytes is None:
        min_bytes = sum(x.nbytes for x in jax.tree.leaves(idx.states)) // S
    for d in devices:
        used = (d.memory_stats() or {}).get("bytes_in_use", 0)
        print(f"device {d.id}: bytes_in_use={used}", flush=True)
        if used < min_bytes:
            fail(f"device {d.id} holds only {used} bytes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--fill", type=int, default=None,
                    help=f"points inserted (default {FILL}; per shard with "
                         f"--chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repro package is not next to this script ({SRC})")
    sys.path.insert(0, SRC)
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    print(f"device: platform={devs[0].platform} kind={kind} "
          f"count={len(devs)}", flush=True)
    if devs[0].platform != "tpu":
        fail("no TPU: JAX found no TPU device, only "
             f"{devs[0].platform!r}")

    from repro.configs.ann import high_recall
    from repro.core import resolve_backend, resolved_hop_fused
    from repro.kernels import ops
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = high_recall(DIM, N_CAP)
    backend = resolve_backend(cfg).name
    interpret = ops._default_interpret()
    hop_fused = resolved_hop_fused(cfg)
    print(f"backend={backend} interpret={interpret} hop_fused={hop_fused} "
          f"n_cap={cfg.n_cap} r={cfg.r} l={cfg.l_search} dim={cfg.dim}",
          flush=True)
    if backend != "pallas" or interpret or hop_fused:
        fail("the chip path must be the compiled pallas per-hop engine")

    clock = Clock(jax)
    if args.chips == 4:
        if len(devs) < 4:
            fail(f"--chips 4 needs 4 devices, found {len(devs)}")
        run_sharded(jax, clock, cfg, args.fill or FILL, args.seed,
                    devices=devs[:4])
        count = 4
    else:
        run_single(jax, clock, cfg, args.fill or FILL, args.seed)
        count = 1
    stats = devs[0].memory_stats() or {}
    print(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": count,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
