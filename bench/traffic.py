"""The one traffic generator: turns a mix file and ``--seed`` into steps.

A mix (``bench/traffic/<mix>.json``) is data:

    {"step": [{"op": "update", "inserts": 128, "deletes": 128},
              {"op": "search", "batches": 1, "batch": 32, "k": 10}],
     "trace_steps": 1}

A closed loop repeats ``step`` until the window closes.  ``update`` is one
kind-major batch of ``inserts`` new points and ``deletes`` of the oldest
live points (a sliding window).  ``search`` is ``batches`` query batches
of ``batch`` queries at ``k``.  New points and queries come from the
configuration's mixture; step ``i`` draws from a generator keyed by
(seed, i), so every seed makes the same sizes and deletes in the same
order and only the vectors differ.

The stream keeps the live set on the host (point ``j`` of the stream is
the ``j``-th inserted, base first, external id ``j mod max_external_id``)
and logs every action, so the reference can replay the live set at each
query's moment after the window.
"""
from __future__ import annotations

import numpy as np

from corpus import centres, mixture, rng

OPS = ("update", "search")


def validate(mix: dict) -> None:
    """Reject a mix file the generator cannot run."""
    if not mix.get("step"):
        raise ValueError("a mix needs a non-empty 'step' list")
    for a in mix["step"]:
        if a.get("op") not in OPS:
            raise ValueError(f"unknown op in mix: {a}")
        if a["op"] == "update":
            if a["inserts"] < 1 or a["deletes"] < 1:
                raise ValueError(f"an update needs inserts and deletes: {a}")
        elif a["batches"] < 1 or a["batch"] < 1 or a["k"] < 1:
            raise ValueError(f"a search needs batches, batch and k: {a}")


class Stream:
    """The actions of one run, generated step by step from the seed."""

    def __init__(self, config: dict, mix: dict, seed: int,
                 base: np.ndarray):
        validate(mix)
        self.mix = mix
        self.seed = seed
        self.metric = config["index"]["metric"]
        self.spread = config["corpus"]["spread"]
        self.max_ext = config["max_external_id"]
        self.cents = centres(config)
        self.chunks = [base]                 # vectors in insertion order
        self.n = len(base)                   # points inserted so far
        self.alive = np.ones(len(base), bool)
        self.log = []                        # ("update", ins_pos, del_pos)
                                             # or ("search", queries, k)

    def ext(self, pos: np.ndarray) -> np.ndarray:
        return (np.asarray(pos) % self.max_ext).astype(np.int64)

    def _grow(self, extra: int) -> None:
        if self.n + extra > len(self.alive):
            more = max(extra, len(self.alive) // 4)
            self.alive = np.concatenate([self.alive, np.zeros(more, bool)])

    def step(self, i: int) -> list:
        """The actions of step ``i``: ``("update", ins_ext, ins_vecs,
        del_ext)`` and ``("search", queries, k)`` tuples, in mix order."""
        out = []
        for j, a in enumerate(self.mix["step"]):
            gen = rng(self.seed, i, j)
            if a["op"] == "search":
                for _ in range(a["batches"]):
                    q = mixture(gen, self.cents, a["batch"], self.spread,
                                self.metric)
                    self.log.append(("search", q, a["k"]))
                    out.append(("search", q, a["k"]))
                continue
            live = np.flatnonzero(self.alive[:self.n])
            n_del = a["deletes"]
            if n_del > len(live):
                raise ValueError("the mix deletes more points than are live")
            victims = live[:n_del]
            vecs = mixture(gen, self.cents, a["inserts"], self.spread,
                           self.metric)
            self._grow(len(vecs))
            ins = np.arange(self.n, self.n + len(vecs))
            self.chunks.append(vecs)
            self.n += len(vecs)
            self.alive[ins] = True
            self.alive[victims] = False
            self.log.append(("update", ins, victims))
            out.append(("update", self.ext(ins), vecs, self.ext(victims)))
        return out

    def vectors(self) -> np.ndarray:
        """Every point of the stream, in insertion order."""
        return np.concatenate(self.chunks)


def replay(log: list, n_base: int, n_total: int):
    """Yield ``(alive_mask, [(queries, k), ...])`` for each stretch of the
    log between updates: the live set that those query batches saw."""
    alive = np.zeros(n_total, bool)
    alive[:n_base] = True
    pending = []
    for entry in log:
        if entry[0] == "search":
            pending.append(entry[1:])
            continue
        if pending:
            yield alive.copy(), pending
            pending = []
        alive[entry[1]] = True
        alive[entry[2]] = False
    if pending:
        yield alive.copy(), pending
