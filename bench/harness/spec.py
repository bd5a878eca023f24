"""Load a cell: its entry in ``BENCHMARK.json`` and the files it names."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration's file
    traffic_name: str
    traffic: dict         # the traffic mix's file
    limits: dict          # limits/<cell>.json
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list
    run_seconds: int


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = _read(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    limits = _read(os.path.join(BENCH, "limits", name + ".json"))
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        run_seconds=int(bench["run_seconds"]))


@functools.cache
def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module: a metric's reader
    (``metrics``) or a sync strategy (``sync``), found by its name."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise KeyError(f"no {kind} file {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_names(root: str = ROOT) -> list[str]:
    return [w["name"] for w in _read(os.path.join(root, "BENCHMARK.json"))
            ["workloads"]]
