"""Batched beam engine vs. vmap-over-while_loop vs. brute force.

Measures the query path end to end at B in {1, 8, 64, 256}:

  * ``batched`` — the natively batched engine (core/search_batched.py):
    one shared hop loop, one fused (B, R) gather-distance tile per hop;
  * ``fused``   — the same engine with multi-hop super-steps forced on
    (``hop_fused=DEFAULT_FUSED_HOPS``): H hop bodies per while_loop
    iteration, so the carry is threaded through the loop machinery 1/H
    as often and XLA fuses across hop boundaries;
  * ``vmap``    — the pre-engine baseline ``search_batch_vmap``
    (vmap of the per-query while_loop: XLA runs every lane to the slowest
    lane's hop count AND select-masks the whole carry each hop);
  * ``brute``   — the exact scan (``brute_force_topk``), the upper bound a
    graph index must beat.

The graph is synthesized (random R-regular adjacency over N random
vectors): beam-search *cost* is governed by degree, beam width and hop
count, not edge quality, and an actual Vamana build at bench scale would
dominate CI wall time.  Engine parity on real graphs is pinned separately
by tests/test_search_batched.py.

Timing is min-over-repeats of one blocked call (this container is a 1-core
CPU box; min is the only robust estimator under scheduler noise).  Writes
``BENCH_search.json`` so the speedup is a recorded artifact; in --smoke
mode non-regression assertions require the batched engine to be at least
as fast as the vmap baseline at B >= 64, and the fused super-steps to be
no slower than the per-hop loop (within 10% CPU-timing slack).

Usage: python -m benchmarks.search_bench [--smoke] [--out BENCH_search.json]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import List

from .common import Row, scale


def _make_state(n: int, dim: int, r: int, seed: int = 0):
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ANNConfig, init_state
    from repro.core.types import write_vectors

    rng = np.random.default_rng(seed)
    data = rng.normal(size=(n, dim)).astype(np.float32)
    adj = rng.integers(0, n, size=(n, r)).astype(np.int32)
    cfg = ANNConfig(dim=dim, n_cap=n, r=r)
    state = init_state(cfg)
    state = state._replace(
        vectors=write_vectors(state.vectors, jnp.arange(n), jnp.asarray(data),
                              dim),
        norms=jnp.sum(jnp.asarray(data) ** 2, axis=1),
        adj=jnp.asarray(adj),
        active=jnp.ones((n,), bool),
        start=jnp.int32(0),
        n_active=jnp.int32(n),
        free_top=jnp.int32(0),
    )
    return cfg, state, rng


def _bench(fn, repeat: int) -> float:
    import jax

    jax.block_until_ready(fn())  # compile + warm
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def run_bench(n: int, dim: int, r: int, l: int, batches, k: int = 10,
              repeat: int = 3) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (
        batched_greedy_search,
        brute_force_topk,
        search_batch_vmap,
    )
    from repro.core.search_batched import DEFAULT_FUSED_HOPS

    cfg, state, rng = _make_state(n, dim, r)
    # same engine, super-steps forced on (the pallas backend auto-selects
    # this; on the CPU jnp backend it must be pinned to be measured)
    fcfg = dataclasses.replace(cfg, hop_fused=DEFAULT_FUSED_HOPS)
    report = {
        "n": n, "dim": dim, "r": r, "l": l, "k": k, "repeat": repeat,
        "note": "random R-regular graph; min-of-repeats wall time; "
                "CPU/interpret numbers off-TPU",
        "batch": {},
    }
    for b in batches:
        qs = jnp.asarray(rng.normal(size=(b, dim)).astype(np.float32))
        bat = jax.jit(
            lambda s, q: batched_greedy_search(s, cfg, q, k=k, l=l)
        )
        fu = jax.jit(
            lambda s, q: batched_greedy_search(s, fcfg, q, k=k, l=l)
        )
        vm = jax.jit(
            lambda s, q: search_batch_vmap(s, cfg, q, k=k, l=l)
        )
        br = jax.jit(
            lambda s, q: brute_force_topk(s, cfg, q, k=k)
        )
        # traversal parity is a precondition for the timing to mean anything
        ids_b = np.asarray(bat(state, qs).topk_ids)
        ids_v = np.asarray(vm(state, qs).topk_ids)
        ids_f = np.asarray(fu(state, qs).topk_ids)
        assert np.array_equal(ids_b, ids_v), (
            f"batched/vmap traversal diverged at B={b}"
        )
        assert np.array_equal(ids_b, ids_f), (
            f"fused super-steps diverged from per-hop engine at B={b}"
        )
        t_bat = _bench(lambda: bat(state, qs), repeat)
        t_fu = _bench(lambda: fu(state, qs), repeat)
        t_vm = _bench(lambda: vm(state, qs), repeat)
        t_br = _bench(lambda: br(state, qs), repeat)
        report["batch"][str(b)] = {
            "batched_ms": t_bat * 1e3,
            "fused_ms": t_fu * 1e3,
            "vmap_ms": t_vm * 1e3,
            "brute_ms": t_br * 1e3,
            "speedup_batched_over_vmap": t_vm / t_bat,
            "speedup_fused_over_batched": t_bat / t_fu,
            "batched_qps": b / t_bat,
            "fused_qps": b / t_fu,
            "vmap_qps": b / t_vm,
        }
    return report


def run(out_path: str = "BENCH_search.json", smoke: bool = False) -> List[Row]:
    if smoke:
        n, dim, r, l = 16384, 64, 32, 48
        batches = (1, 8, 64)
        repeat = 3
    else:
        n = scale(16_384, 65_536)
        dim = scale(64, 128)
        r, l = 32, 48
        batches = (1, 8, 64, 256)
        repeat = scale(3, 5)
    report = run_bench(n, dim, r, l, batches, repeat=repeat)
    report["smoke"] = smoke
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)

    rows: List[Row] = []
    for b, stats in report["batch"].items():
        rows.append(Row(
            f"search_bench.B{b}",
            stats["batched_ms"] * 1e3,
            f"speedup_over_vmap={stats['speedup_batched_over_vmap']:.2f};"
            f"fused_over_batched={stats['speedup_fused_over_batched']:.2f};"
            f"batched_qps={stats['batched_qps']:.0f};"
            f"fused_qps={stats['fused_qps']:.0f};"
            f"brute_ms={stats['brute_ms']:.1f}",
        ))
    rows.append(Row("search_bench.report", 0.0, f"written={out_path}"))

    if smoke:
        # non-regression gate: the batched engine must not lose to the
        # baseline it replaced at serving batch sizes
        for b, stats in report["batch"].items():
            if int(b) >= 64:
                assert stats["batched_ms"] <= stats["vmap_ms"], (
                    f"batched engine regressed at B={b}: "
                    f"{stats['batched_ms']:.1f} ms vs vmap "
                    f"{stats['vmap_ms']:.1f} ms"
                )
                # the multi-hop super-step must not lose to the per-hop
                # loop it wraps (10% slack: CPU timings on a 1-core box)
                assert stats["fused_ms"] <= stats["batched_ms"] * 1.10, (
                    f"fused super-steps regressed at B={b}: "
                    f"{stats['fused_ms']:.1f} ms vs per-hop "
                    f"{stats['batched_ms']:.1f} ms"
                )
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + the batched<=vmap regression gate")
    ap.add_argument("--out", default="BENCH_search.json")
    args = ap.parse_args()
    for row in run(out_path=args.out, smoke=args.smoke):
        print(row.csv())
