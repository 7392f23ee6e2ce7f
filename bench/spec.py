"""Find a cell's parts by name: its configuration, traffic mix and metrics.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name ``BENCHMARK.json``
gives it:

    bench/configs/<config>.json    a deployment's sizes, guarantees, limits
    bench/traffic/<mix>.json       a traffic mix's parameters
    bench/metrics/<metric>.py      a reader with ``read(run) -> float | None``

so a later change adds a cell, a mix or a metric as new files plus new
entries, without editing a file that is already here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic: str
    chips: int
    config: dict           # the configuration file as run
    config_path: str
    mix: dict              # the traffic mix file
    end_to_end: tuple      # metric entries that this cell reports
    per_layer: tuple


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    c = configs[w["config"]]
    config_path = os.path.join(root, c["file"])
    with open(config_path) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           w["traffic"] + ".json")) as f:
        mix = json.load(f)
    return Cell(
        name=name, config_name=w["config"], traffic=w["traffic"],
        chips=int(w["chips"]), config=config, config_path=config_path,
        mix=mix,
        end_to_end=tuple(m for m in bench["end_to_end"] if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _reports(m, name)),
    )


def load_reader(metric: str, root: str = ROOT):
    """The ``read`` function of ``<root>/bench/metrics/<metric>.py``.  The
    file is loaded by path, since a metric's name may hold dots."""
    folder = os.path.join(root, "bench", "metrics")
    if folder not in sys.path:
        sys.path.insert(0, folder)      # for the readers' shared helpers
    path = os.path.join(folder, metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(device_kind: str, root: str = ROOT) -> dict:
    """The device's published peaks; a device not in the table is an
    error, never a default."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(table['devices'])}")
    return table["devices"][device_kind]


def ann_config(config: dict):
    """The program's ``ANNConfig`` for a configuration file."""
    from repro.core.types import ANNConfig

    fields = {f.name for f in dataclasses.fields(ANNConfig)}
    return ANNConfig(**{k: v for k, v in config["index"].items()
                        if k in fields})
