"""Distributed serving: the two-tower retrieval arch composed with the
paper's streaming index, on a shard_map fan-out over the devices at hand
(8 host devices under JAX_PLATFORMS=cpu) — candidate embeddings stream in
and out while queries run.

  retrieval path A: exact fused matmul+top-k (repro.kernels.topk_score)
  retrieval path B: sharded IP-DiskANN graph index (sub-linear search)

    JAX_PLATFORMS=cpu python examples/distributed_serving.py
    python examples/distributed_serving.py --shards 4     # on 4 chips
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.ann import test_scale
from repro.core.distributed import ShardedIndex
from repro.kernels.ops import topk_search
from repro.launch.mesh import shard_mesh
from repro.models.recsys import TwoTowerConfig, init_two_tower_params, _mlp


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=8)
    args = ap.parse_args()
    # the mesh first: on CPU its host devices must precede backend start-up
    mesh = shard_mesh(args.shards)
    n_items, dim = 4000, 64
    cfg_tt = TwoTowerConfig(name="demo", embed_dim=dim,
                            tower_mlp=(128, 64, 32),
                            user_vocab=1000, item_vocab=n_items)
    params = init_two_tower_params(jax.random.PRNGKey(0), cfg_tt)

    # item-tower embeddings = the streaming corpus
    item_embs = np.asarray(_mlp(params["item_tower"], params["item_emb"]))
    print(f"embedded {n_items} items -> {item_embs.shape[1]}-d")

    # --- path A: exact scoring with the fused Pallas top-k kernel ----------
    user_vec = np.asarray(
        _mlp(params["user_tower"], params["user_emb"][:1])
    )
    t0 = time.perf_counter()
    dists, ids = topk_search(
        jnp.asarray(user_vec), jnp.asarray(item_embs), k=10, metric="ip",
        tile_n=512,
    )
    print(f"exact top-10 (fused kernel): {ids[0][:5].tolist()}... "
          f"in {time.perf_counter()-t0:.2f}s")

    # --- path B: sharded streaming graph index ------------------------------
    # external-id semantics end to end: the sharded index rides the same
    # unified apply(state, UpdateBatch) front door as StreamingIndex
    cfg = test_scale(item_embs.shape[1], n_cap=n_items, metric="ip")
    idx = ShardedIndex(cfg, mesh)
    ext = np.arange(n_items)
    idx.insert(ext, item_embs)
    print(f"sharded index built over {mesh.size} shards")

    found, gshards, gdists, comps = idx.search(user_vec, k=10, l=32)
    exact = set(int(i) for i in np.asarray(ids)[0])
    overlap = len(exact.intersection(found[0].tolist())) / 10
    print(f"graph fan-out top-10: {found[0][:5].tolist()}... "
          f"recall vs exact = {overlap:.1f}, comps = {comps} "
          f"(vs {n_items} brute-force)")

    # --- streaming churn: delete half the catalogue, serve again -----------
    drop = ext[::2]
    idx.delete(drop)
    found2, _, _, _ = idx.search(user_vec, k=10, l=32)
    assert not set(found2[0].tolist()).intersection(set(drop.tolist())), \
        "deleted items served!"
    print(f"after deleting {len(drop)} items in place: "
          f"top-10 contains no deleted items — OK")


if __name__ == "__main__":
    main()
