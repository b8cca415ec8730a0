"""Cell loading: everything a cell is made of is found by name.

`BENCHMARK.json` names a workload's configuration (its `file`) and traffic mix
(`<path>/traffic/<traffic>.json`); the mix's `kind` names its driver
(`<path>/drivers/<kind>.py`); each per-layer metric names its reader
(`<path>/layer_metrics/<name>.py`). `<path>` is any entry of the file's
`paths`, relative to the directory that holds it, so a later PR adds a
configuration, a mix, a driver, a reader or a cell by adding files and an
entry: no file that exists needs an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys

@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    root: str                 # directory of BENCHMARK.json
    paths: tuple              # its `paths`, relative to root
    config_name: str
    config: dict              # the configuration file, as it is run
    traffic_name: str
    traffic_file: str
    traffic: dict             # the traffic file's content
    end_to_end: tuple         # metric entries that apply to this cell
    per_layer: tuple

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def load_benchmark(bench_file: str) -> dict:
    with open(bench_file) as f:
        return json.load(f)


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def find_under_paths(root: str, paths, sub: str, filename: str) -> str:
    """`<root>/<path>/<sub>/<filename>` in the first path that has it."""
    tried = []
    for p in paths:
        cand = os.path.normpath(os.path.join(root, p, sub, filename))
        if os.path.isfile(cand):
            return cand
        tried.append(cand)
    raise FileNotFoundError(f"{sub}/{filename} not under any of paths: {tried}")


def load_cell(bench_file: str, workload: str) -> Cell:
    bench = load_benchmark(bench_file)
    root = os.path.dirname(os.path.abspath(bench_file))
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        names = [w["name"] for w in bench["workloads"]]
        raise KeyError(f"no workload {workload!r} in {bench_file}: {names}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(root, cfg_entry["file"])) as f:
        config = json.load(f)
    traffic_file = find_under_paths(root, bench["paths"], "traffic",
                                    entry["traffic"] + ".json")
    with open(traffic_file) as f:
        traffic = json.load(f)
    return Cell(
        name=workload, chips=int(entry["chips"]), root=root,
        paths=tuple(bench["paths"]), config_name=entry["config"],
        config=config, traffic_name=entry["traffic"],
        traffic_file=traffic_file, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if applies(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"] if applies(m, workload)))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module      # dataclasses look their module up there
    spec.loader.exec_module(module)
    return module


def load_driver(cell: Cell):
    """The module that runs mixes of this cell's `kind`: it has
    `run(cell, opts) -> RunResult`."""
    path = find_under_paths(cell.root, cell.paths, "drivers",
                            cell.kind + ".py")
    return load_module(path, f"bench_driver_{cell.kind}")


def read_layer_metrics(cell: Cell, run: dict, only_reported: set) -> dict:
    """One reader per per-layer metric, found by the metric's name. A reader
    that finds nothing to read returns None and the metric is left out. A
    per-layer metric is reported only where the metric it moves is."""
    out = {}
    for m in cell.per_layer:
        if m["moves"] not in only_reported:
            continue
        path = find_under_paths(cell.root, cell.paths, "layer_metrics",
                                m["name"] + ".py")
        reader = load_module(path, "bench_layer_metric_" + m["name"].replace(
            ".", "_").replace("-", "_"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
