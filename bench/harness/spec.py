"""Find a cell's files by name.

``BENCHMARK.json`` at the checkout root lists the cells. A cell named
``<cell>`` with configuration ``<config>`` and traffic ``<mix>`` reads

    bench/workloads/<cell>.json   engine settings, trace length, check
    bench/configs/<config>.json   the model as it is run
    bench/traffic/<mix>.json      the parameters of the traffic generator

and its metrics are the readers ``bench/metrics/<metric>.py`` of the
entries of ``end_to_end`` (untraced runs) and ``per_layer`` (traced runs)
that list the cell, or list no cells at all.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json
    traffic: dict         # bench/traffic/<mix>.json
    settings: dict        # bench/workloads/<cell>.json
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list

    @property
    def engine(self) -> dict:
        return self.settings["engine"]


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entries = [w for w in bench["workloads"] if w["name"] == name]
    if not entries:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    w = entries[0]
    bdir = root / "bench"
    return Cell(
        name=name, chips=int(w["chips"]),
        config=load_json(bdir / "configs" / f"{w['config']}.json"),
        traffic=load_json(bdir / "traffic" / f"{w['traffic']}.json"),
        settings=load_json(bdir / "workloads" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def load_module(kind: str, name: str, root: Path = ROOT):
    """bench/<kind>/<name>.py as a module (metric readers, references,
    program adapters)."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
