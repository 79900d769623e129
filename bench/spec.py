"""Find a cell's files by the names ``BENCHMARK.json`` gives them."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload with everything it names, loaded from ``root``."""

    root: str
    workload: dict
    config: dict  # the configuration file's contents
    traffic: dict  # the traffic mix's parameters
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``; raises
    ``KeyError`` for a name the file does not hold."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    wl = {w["name"]: w for w in spec["workloads"]}[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[wl["config"]]
    return Cell(
        root=root,
        workload=wl,
        config=_read_json(os.path.join(root, cfg_entry["file"])),
        traffic=_read_json(
            os.path.join(root, "bench", "traffic", wl["traffic"] + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
    )


def metric_reader(root: str, name: str):
    """The ``read(ctx)`` function of ``<root>/bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
