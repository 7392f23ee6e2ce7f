"""Distance-backend throughput: jnp vs pallas(interpret) vs ref.

Measures the two HBM-bound primitives the backend layer routes:

  * ``dists_to_ids``     — the beam-loop gather+distance (R-neighbour shape)
  * ``brute_force_topk`` — the exact-scan recall oracle

and writes ``BENCH_backend.json`` so future PRs have a perf trajectory for
the dispatch seam.  On this CPU container the pallas numbers are interpret
mode (Python-executed kernel bodies) — they are a correctness trace, not a
speed claim; on TPU the same code path Mosaic-compiles.

Usage: python -m benchmarks.backend_bench [--out BENCH_backend.json]
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

from .common import Row, scale


def _bench_backend(name: str, state, cfg_base, ids, q, queries, k: int,
                   repeat: int) -> Dict[str, float]:
    import dataclasses

    import jax

    from repro.core import brute_force_topk, get_backend

    cfg = dataclasses.replace(cfg_base, backend=name)
    be = get_backend(name)

    gather = jax.jit(
        lambda s, qv, i: be.dists_to_ids(s, cfg, qv, i)
    )
    jax.block_until_ready(gather(state, q, ids))      # compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = gather(state, q, ids)
    jax.block_until_ready(out)
    gather_s = (time.perf_counter() - t0) / repeat

    jax.block_until_ready(brute_force_topk(state, cfg, queries, k=k))
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = brute_force_topk(state, cfg, queries, k=k)
    jax.block_until_ready(out)
    topk_s = (time.perf_counter() - t0) / repeat

    return {
        "gather_us_per_call": gather_s * 1e6,
        "gather_dists_per_s": ids.shape[0] / gather_s,
        "brute_topk_us_per_call": topk_s * 1e6,
        "brute_topk_dists_per_s": queries.shape[0] * state.norms.shape[0]
        / topk_s,
    }


def run(out_path: str = "BENCH_backend.json",
        backends=("jnp", "pallas", "ref")) -> List[Row]:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ANNConfig, init_state, make_dataset
    from repro.core.types import write_vectors

    n = scale(2048, 65_536)
    dim = scale(64, 128)
    r = scale(32, 64)
    data, queries = make_dataset(n, dim, n_queries=scale(8, 64), seed=13)
    cfg = ANNConfig(dim=dim, n_cap=n, r=r)
    state = init_state(cfg)
    state = state._replace(
        vectors=write_vectors(state.vectors, jnp.arange(n),
                              jnp.asarray(data), dim),
        norms=jnp.sum(jnp.asarray(data) ** 2, axis=1),
        active=jnp.ones((n,), bool),
    )
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, n, size=(r,)).astype(np.int32))
    q = jnp.asarray(queries[0])
    qs = jnp.asarray(queries)

    report = {
        "n": n, "dim": dim, "gather_width": r,
        "note": "pallas numbers are interpret mode off-TPU",
        "backends": {},
    }
    rows: List[Row] = []
    for name in backends:
        # interpret-mode brute-force over the full table is slow; fewer reps
        repeat = 50 if name == "jnp" else 5
        stats = _bench_backend(name, state, cfg, ids, q, qs, k=10,
                               repeat=repeat)
        report["backends"][name] = stats
        rows.append(Row(
            f"backend_bench.{name}",
            stats["gather_us_per_call"],
            f"gather_dists_per_s={stats['gather_dists_per_s']:.0f};"
            f"brute_topk_dists_per_s={stats['brute_topk_dists_per_s']:.0f}",
        ))
    if "jnp" in report["backends"] and "pallas" in report["backends"]:
        report["pallas_over_jnp_gather"] = (
            report["backends"]["jnp"]["gather_us_per_call"]
            / report["backends"]["pallas"]["gather_us_per_call"]
        )
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    rows.append(Row("backend_bench.report", 0.0, f"written={out_path}"))
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_backend.json")
    args = ap.parse_args()
    for row in run(out_path=args.out):
        print(row.csv())
