"""Sharded update streams: owner-compacted vs replicate-and-mask routing.

A 2-shard ``ShardedIndex`` runs the SAME chained T-step insert/delete
stream through ``update_stream`` under both routings (final stacked states
asserted bit-identical before timing):

  * ``replicate`` — the pre-rework layout: every shard receives all B
    lanes of every op and masks the half it does not own, so the per-shard
    scan stays B lanes wide no matter how many shards exist;
  * ``compact``   — the shard-native layout: the host packs each shard's
    owned lanes into a power-of-two (S, T, Bc) sub-tensor
    (``core/api.py::compact_owner_segment``), so each shard scans
    Bc = next_bucket(ceil(B/S)) lanes — the host packing cost is part of
    the measured path.

Both per-shard visibility modes are measured, because they price masked
lanes completely differently:

  * ``sequential=False`` (batched phases): a replicated batch's masked
    lanes still pay full (B, R) beam-tile width in the shared hop loop,
    so compaction shrinks real per-shard compute S-fold — this is the
    regime the compact layout exists for (measured ~1.4x at S=2 on this
    box);
  * ``sequential=True`` (the paper's serial concurrency model): masked
    lanes early-exit their per-lane ``lax.cond``, so replicate-and-mask
    is already nearly free per masked lane and compact is wall-clock
    neutral on CPU (the structural win — S-fold shorter scans and
    op tensors — shows on accelerators, not here).

External ids are pre-balanced across the 2 shards so every batch owns
exactly B/S lanes per shard (the steady-state of hash routing at scale);
the bench then isolates the scan-width mechanism instead of hash luck.
Timing is interleaved min-of-repeats (``update_bench`` discipline: box
noise on a 1-core-class CI machine swings >10%, so every path samples
every round).  On an accelerator the bench runs in the calling process on
the first two of ``jax.devices()`` (and refuses on a one-device host); on
CPU it runs in a child process whose two devices are forced host devices,
so that flag cannot leak into the caller's JAX runtime.

Results merge into ``BENCH_update.json`` under the ``"shard"`` key.  In
--smoke mode the gates run on aggregate min-of-repeats only: batched-mode
compact must beat replicate with 5% slack (the real win), and
sequential-mode compact must not regress past 10% slack (the
update_bench noise allowance).

Usage: python -m benchmarks.shard_bench [--smoke] [--out BENCH_update.json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
from typing import List

from .common import REPO, Row, scale

def _bench(params: dict, devices) -> dict:
    """The measurement, in the calling process, on the first two
    ``devices``."""
    import time

    import jax
    import numpy as np
    from repro.core import ANNConfig, clone_state, delete_batch, insert_batch
    from repro.core.distributed import ShardedIndex

    S, T, B = 2, params["T"], params["B"]
    repeat = params["repeat"]
    cfg = ANNConfig(dim=params["dim"], n_cap=params["n_cap"], r=params["r"],
                    l_build=params["l"], l_search=params["l"],
                    l_delete=params["l"], k_delete=params["k_delete"],
                    n_copies=2, consolidation_threshold=1e9)
    mesh = jax.make_mesh((S,), ("shard",), devices=devices[:S])
    rng = np.random.default_rng(0)

    # pre-balanced external ids: every B-lane batch owns B/S per shard
    pool = np.arange(params["n_ids"])
    own = ShardedIndex(cfg, mesh, max_external_id=1).route(pool)
    per = [pool[own == s] for s in range(S)]
    half = B // S

    def batch_ids(i):
        return np.concatenate([p[i * half:(i + 1) * half] for p in per])

    n_boot = T  # bootstrap batches, then T/2 delete + T/2 insert stream ops
    data = rng.normal(size=(params["n_ids"], cfg.dim)).astype(np.float32)
    boot = [insert_batch(batch_ids(i), data[batch_ids(i)])
            for i in range(n_boot)]
    stream = []
    for t in range(T // 2):
        stream.append(delete_batch(batch_ids(t), cfg.dim))
        new = batch_ids(n_boot + t)
        stream.append(insert_batch(new, data[new]))

    out = {"S": S, "T": T, "B": B, "repeat": repeat, "mode": {},
           "device": {"platform": devices[0].platform,
                      "kind": devices[0].device_kind, "count": S}}
    for sequential in (False, True):
        idxs = {}
        for routing in ("compact", "replicate"):
            idx = ShardedIndex(cfg, mesh, routing=routing,
                               sequential=sequential,
                               max_external_id=params["n_ids"])
            idx.update_stream(boot, max_t=n_boot)
            idxs[routing] = (idx, clone_state(idx.states))

        def run(routing):
            idx, start = idxs[routing]
            idx.states = clone_state(start)
            idx.update_stream(stream, max_t=T)
            jax.block_until_ready(idx.states.graph.adj)

        # semantics parity is a precondition for timing to mean anything
        run("compact")
        run("replicate")
        for x, y in zip(jax.tree.leaves(idxs["compact"][0].states),
                        jax.tree.leaves(idxs["replicate"][0].states)):
            assert np.array_equal(np.asarray(x), np.asarray(y)), (
                f"compact / replicate diverged (sequential={sequential})")

        # interleaved min-of-repeats (update_bench discipline)
        best = {"compact": float("inf"), "replicate": float("inf")}
        for _ in range(repeat):
            for name in ("compact", "replicate"):
                t0 = time.perf_counter()
                run(name)
                best[name] = min(best[name], time.perf_counter() - t0)

        n_updates = T * B
        key = "sequential" if sequential else "batched"
        out["mode"][key] = {
            "replicate_ms": best["replicate"] * 1e3,
            "compact_ms": best["compact"] * 1e3,
            "speedup_compact_over_replicate":
                best["replicate"] / best["compact"],
            "replicate_updates_per_s": n_updates / best["replicate"],
            "compact_updates_per_s": n_updates / best["compact"],
        }
    return out


# Off an accelerator the two shards are forced host devices, which only a
# fresh process can configure (XLA reads the flag at backend start-up).
CPU_CHILD = textwrap.dedent("""
    import json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import warnings; warnings.filterwarnings("ignore")
    import jax
    from benchmarks.shard_bench import _bench
    print(json.dumps(_bench(json.loads(sys.argv[1]), jax.devices())))
""")


def run_bench(n_cap: int, dim: int, r: int, t_steps: int, b: int,
              repeat: int, l: int = 16, k_delete: int = 8) -> dict:
    params = {
        "n_cap": n_cap, "dim": dim, "r": r, "T": t_steps, "B": b,
        "repeat": repeat, "l": l, "k_delete": k_delete,
        # enough balanced ids for bootstrap + stream inserts
        "n_ids": (t_steps + t_steps // 2 + 2) * b,
    }
    import jax

    if jax.default_backend() != "cpu":
        # an accelerator belongs to this process: measure here, never in
        # a child that would have to take the device from us
        devices = jax.devices()
        if len(devices) < 2:
            raise RuntimeError(
                f"shard_bench needs 2 devices; this host has "
                f"{len(devices)} {devices[0].platform} device"
            )
        report = _bench(params, devices)
    else:
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join([REPO, os.path.join(REPO, "src")])
        out = subprocess.run(
            [sys.executable, "-c", CPU_CHILD, json.dumps(params)],
            env=env, capture_output=True, text=True, timeout=1800, cwd=REPO,
        )
        if out.returncode != 0:
            raise RuntimeError(f"shard bench subprocess failed:\n"
                               f"{out.stderr[-3000:]}")
        report = json.loads(out.stdout.strip().splitlines()[-1])
    report["note"] = (
        "2-shard chained update_stream, balanced ownership; compact = "
        "owner-packed (S, T, Bc) sub-batches, replicate = full-B masked "
        "lanes; batched mode is where masked lanes pay tile width; min of "
        "interleaved repeats; device in report['device']"
    )
    return report


def run(out_path: str = "BENCH_update.json", smoke: bool = False) -> List[Row]:
    if smoke:
        n_cap, dim, r, l, k = 2048, 16, 8, 16, 8
        t_steps, b, repeat = 16, 64, 3
    else:
        n_cap = scale(2048, 16_384)
        dim = scale(32, 64)
        r = scale(16, 32)
        l, k = 32, 16
        t_steps, b, repeat = 16, 64, scale(3, 5)
    report = run_bench(n_cap, dim, r, t_steps, b, repeat, l=l, k_delete=k)

    # merge under the update bench's report file: one JSON carries the
    # whole update-throughput story (per-op, segment, sharded)
    merged = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            merged = json.load(f)
    merged["shard"] = report
    with open(out_path, "w") as f:
        json.dump(merged, f, indent=2)

    rows: List[Row] = []
    for mode, stats in report["mode"].items():
        rows.append(Row(
            f"shard_bench.S{report['S']}.B{report['B']}.{mode}",
            stats["compact_ms"] * 1e3,
            f"T={report['T']};"
            f"compact_over_replicate="
            f"{stats['speedup_compact_over_replicate']:.2f};"
            f"compact_updates_per_s={stats['compact_updates_per_s']:.0f};"
            f"replicate_updates_per_s="
            f"{stats['replicate_updates_per_s']:.0f}",
        ))
    rows.append(Row("shard_bench.report", 0.0, f"merged={out_path}"))

    if smoke:
        # aggregate/min-of-repeats gates only (1-core box noise >10%)
        bat = report["mode"]["batched"]
        seq = report["mode"]["sequential"]
        # batched phases: masked lanes pay (B, R) tile width, so the
        # owner-compacted layout must genuinely win (measured ~1.4x)
        assert bat["compact_ms"] <= bat["replicate_ms"] * 1.05, (
            f"compact routing lost to replicate-and-mask in batched mode: "
            f"{bat['compact_ms']:.1f} ms vs {bat['replicate_ms']:.1f} ms"
        )
        # serial scans: masked lanes early-exit, so compact is expected
        # wall-clock neutral here — gate non-regression with noise slack
        assert seq["compact_ms"] <= seq["replicate_ms"] * 1.10, (
            f"compact routing regressed sequential streams: "
            f"{seq['compact_ms']:.1f} ms vs {seq['replicate_ms']:.1f} ms"
        )
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + compact-vs-replicate gates")
    ap.add_argument("--out", default="BENCH_update.json")
    args = ap.parse_args()
    for row in run(out_path=args.out, smoke=args.smoke):
        print(row.csv())
