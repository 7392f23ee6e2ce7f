"""One run of one cell: set-up, the measured window, the check, the result.

The window drives the program's own entry points on the restored base
index: ``repro.core.apply_segment`` for each update batch (one kind-major
mixed batch per call, blocked until its per-lane results are on the host)
and ``repro.core.search_index`` for each query batch (from the call until
ids, distances and counters are on the host).  A closed loop repeats the
mix's step until ``seconds`` have passed; the step in flight at the
deadline runs to its end and counts whole, with its time.
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import time

import numpy as np

import corpus
import reference
import spec
import trace_reduce
import traffic

# lowering to MLIR and the backend compile (chip_smoke.py's Clock): a
# compile inside the window shows here
_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)

class CompileClock:
    """Seconds and events of JAX lowering and compiling, as they happen."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.events += 1


@dataclasses.dataclass
class Run:
    """What a run recorded; the per-layer readers take their numbers from
    it.  ``updates``/``searches`` hold one dict per call, in order."""

    cell: spec.Cell
    cfg: object
    peaks: dict
    updates: list = dataclasses.field(default_factory=list)
    searches: list = dataclasses.field(default_factory=list)
    elapsed_s: float = 0.0
    trace: dict = None
    traced_window_s: float = 0.0


def p95(values) -> float:
    """95th percentile of every sample (numpy's linear interpolation)."""
    return float(np.percentile(np.asarray(values, np.float64), 95))


def update_batch(ins_ext, vecs, del_ext, dim: int):
    """``(ops, split)``: one kind-major batch as a one-op segment."""
    from repro.core import mixed_update_batch
    from repro.core.types import stack_update_batches

    batch, split = mixed_update_batch(ins_ext, vecs, del_ext, dim)
    return stack_update_batches([batch]), split


class Client:
    """Calls into the program for one cell; ``warm`` compiles what ``act``
    will run, on all-masked updates so the base index stays as built."""

    def __init__(self, jax, run: Run, state):
        self.jax = jax
        self.run = run
        self.state = state
        self.policy = run.cell.config["policy"]
        self.dim = run.cell.config["index"]["dim"]

    def update(self, ins_ext, vecs, del_ext, *, traced=False, warm=False):
        from repro.core import apply_segment

        jax = self.jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.update.prepare"):
            ops, split = update_batch(ins_ext, vecs, del_ext, self.dim)
            if warm:
                ops = ops._replace(valid=ops.valid & False)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.update.call"):
            self.state, res = apply_segment(self.state, self.run.cfg, ops,
                                            policy=self.policy, split=split)
        t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.update.wait"):
            ok, comps, kind, valid = jax.device_get(
                (res.ok[0], res.n_comps[0], ops.kind[0], ops.valid[0]))
        t3 = time.perf_counter()
        if not warm:
            self.run.updates.append({
                "ok": ok[valid], "n_comps": comps[valid],
                "kind": kind[valid], "call_s": t2 - t1, "wall_s": t3 - t0,
                "traced": traced,
            })

    def search(self, queries, k, *, traced=False, warm=False):
        from repro.core import search_index

        jax = self.jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.query.call"):
            ext, dists, res = search_index(
                self.state, self.run.cfg, jax.numpy.asarray(queries), k=k)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.query.wait"):
            ext, dists, hops, comps = jax.device_get(
                (ext, dists, res.n_hops, res.n_comps))
        t2 = time.perf_counter()
        if not warm:
            self.run.searches.append({
                "ext": ext, "dists": dists, "n_hops": hops,
                "n_comps": comps, "call_s": t1 - t0, "wall_s": t2 - t0,
                "traced": traced,
            })

    def act(self, action, **kw):
        if action[0] == "update":
            self.update(*action[1:], **kw)
        else:
            self.search(*action[1:], **kw)


def warm_up(client: Client, mix: dict, config: dict) -> None:
    """Compile every shape of the mix's step, and no other."""
    dim = config["index"]["dim"]
    base = config["live_points"]
    for a in mix["step"]:
        if a["op"] == "search":
            client.search(np.zeros((a["batch"], dim), np.float32), a["k"],
                          warm=True)
        else:
            client.update(np.arange(a["inserts"]),
                          np.zeros((a["inserts"], dim), np.float32),
                          np.arange(base - a["deletes"], base), warm=True)


def window(client: Client, stream: traffic.Stream, seconds: float,
           trace_dir: str = None) -> None:
    """The measured closed loop.  With ``trace_dir``, the profiler records
    the first ``trace_steps`` steps of the mix."""
    jax = client.jax
    run = client.run
    trace_steps = run.cell.mix.get("trace_steps", 1)
    tracing = trace_dir is not None
    if tracing:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    deadline = t0 + seconds
    i = 0
    while True:
        with jax.profiler.TraceAnnotation("bench.traffic"):
            actions = stream.step(i)
        for a in actions:
            client.act(a, traced=tracing)
        i += 1
        if tracing and (i >= trace_steps or time.perf_counter() >= deadline):
            run.traced_window_s = time.perf_counter() - t0
            jax.profiler.stop_trace()
            tracing = False
        if time.perf_counter() >= deadline:
            break
    run.elapsed_s = time.perf_counter() - t0
    jax.block_until_ready(client.state)


def end_to_end(run: Run, checks: dict, setup_s: float) -> dict:
    """Every end-to-end number this run can give, by quantity.  A metric
    named ``<quantity>.<qualifier>`` reports its quantity under a bound of
    its own (``recall_at_10.search``)."""
    out = {"setup_s": setup_s, "recall_at_10": 1.0 - checks["recall_miss"]}
    if run.updates:
        applied = sum(int(u["ok"].sum()) for u in run.updates)
        out["update_ops_per_s"] = applied / run.elapsed_s
    if run.searches:
        out["queries_per_s"] = (sum(len(s["ext"]) for s in run.searches)
                                / run.elapsed_s)
        out["query_p95_ms"] = p95([s["wall_s"] * 1e3 for s in run.searches])
    return out


def per_layer(run: Run, root: str) -> dict:
    out = {}
    for m in run.cell.per_layer:
        value = spec.load_reader(m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: spec.Cell, *, seed: int, seconds: float, trace: bool,
             devices: list, t_start: float, root: str = spec.ROOT,
             control: bool = False) -> dict:
    """Set up, measure, check; returns the result line as a dict.

    ``control`` answers the window's queries with the bfloat16 reference
    in the program's place (a control run for the limits, never part of
    the benchmark's own runs)."""
    import jax

    kind = devices[0].device_kind
    cfg = spec.ann_config(cell.config)
    peaks = spec.load_peaks(kind, root) if devices[0].platform == "tpu" \
        else {}
    run = Run(cell=cell, cfg=cfg, peaks=peaks)
    clock = CompileClock(jax)

    saved, built_s = corpus.restore_or_build(cell, cfg, kind, log=log)
    if built_s is not None:
        log(f"setup: first run in this checkout, base build {built_s:.1f} s")
    from repro.core import init_index_state

    like = jax.eval_shape(lambda: init_index_state(
        cfg, cell.config["max_external_id"]))
    state = corpus.to_device(saved, like)
    base = corpus.base_points(cell.config)
    stream = traffic.Stream(cell.config, cell.mix, seed, base)
    client = Client(jax, run, state)
    warm_up(client, cell.mix, cell.config)
    jax.block_until_ready(client.state)
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.3f} s (compile {clock.seconds:.1f} s)")

    trace_dir = None
    if trace:
        trace_dir = os.path.join(spec.BENCH_DIR, ".cache", "trace",
                                 cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    c_events, c_secs = clock.events, clock.seconds
    window(client, stream, seconds, trace_dir)
    log(f"window: {run.elapsed_s:.3f} s, {len(run.updates)} update calls, "
        f"{len(run.searches)} query batches, compiles inside "
        f"{clock.events - c_events} ({clock.seconds - c_secs:.2f} s)")
    for name, calls in (("update", run.updates), ("query", run.searches)):
        if calls:
            wall = np.array([c["wall_s"] for c in calls]) * 1e3
            log(f"{name} calls: n={len(wall)} wall_ms min={wall.min():.2f} "
                f"median={np.median(wall):.2f} max={wall.max():.2f}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    del client.state, state
    if trace:
        run.trace = trace_reduce.load_xplane(trace_dir)

    failed_ops = sum(int((~u["ok"]).sum()) for u in run.updates)
    answers = [(s["ext"], s["dists"]) for s in run.searches]
    checks, bad_queries = reference.compare(stream, answers, failed_ops,
                                            control=control)
    correct, shown = reference.judge(checks, cell.config["limits"])

    device = {"platform": devices[0].platform, "kind": kind,
              "count": cell.chips, "memory_peak_bytes": int(peak)}
    result = {
        "correct": bool(correct),
        "attempted": sum(len(u["ok"]) for u in run.updates)
        + sum(len(s["ext"]) for s in run.searches),
        "failed": failed_ops + bad_queries,
    }
    if trace:
        device["busy_s"] = trace_reduce.busy_s(run.trace)
        device["window_s"] = run.traced_window_s
        result["metrics"] = per_layer(run, root)
        result["device"] = device
        result["breakdown"] = trace_reduce.breakdown(run.trace)
    else:
        e2e = end_to_end(run, checks, setup_s)
        result["metrics"] = {
            m["name"]: {"value": float(e2e[m["name"].split(".")[0]]),
                        "unit": m["unit"]}
            for m in cell.end_to_end
        }
        result["device"] = device
    result["checks"] = shown
    for n, v in shown.items():
        log(f"check {n}: {v['value']!r} limit {v['limit']!r}")
    return result
