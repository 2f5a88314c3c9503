"""Finds a cell's parts by name: BENCHMARK.json at the checkout root, the
configuration file it names, ``traffic/<traffic>.json``, the load kind
``loads/<kind>.py`` and the per-layer readers ``metrics/<metric>.py``.
A new cell, mix, load kind or metric is new files and new entries; no
code here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list       # metric entries this cell reports, --trace 0
    per_layer: list        # metric entries this cell reports, --trace 1


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_file: pathlib.Path | None = None) -> Cell:
    bench = _load_json(bench_file or ROOT / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    cell = load_parts(w["config"], w["traffic"], int(w["chips"]), bench)
    cell.name = name
    cell.end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
    cell.per_layer = [m for m in bench["per_layer"] if _applies(m, name)]
    return cell


def load_parts(config_name: str, traffic_name: str, chips: int = 1,
               bench: dict | None = None) -> Cell:
    """A cell made of a configuration (by its BENCHMARK.json name, else
    ``configs/<name>.json``) and a traffic mix, with no metrics: what the
    knee sweep drives before a cell exists."""
    bench = bench or _load_json(ROOT / "BENCHMARK.json")
    files = {c["name"]: ROOT / c["file"] for c in bench["configs"]}
    cfg_file = files.get(config_name,
                         BENCH_DIR / "configs" / f"{config_name}.json")
    return Cell(name=f"{config_name}.{traffic_name}", chips=chips,
                config_name=config_name, config=_load_json(cfg_file),
                traffic_name=traffic_name,
                traffic=_load_json(BENCH_DIR / "traffic"
                                   / f"{traffic_name}.json"),
                end_to_end=[], per_layer=[])


def _module(path: pathlib.Path, label: str):
    if not path.is_file():
        raise SystemExit(f"no {label} at {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{label}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str):
    """The load generator module ``loads/<kind>.py``."""
    return _module(BENCH_DIR / "loads" / f"{kind}.py", "load")


def metric_reader(name: str):
    """The per-layer reader ``metrics/<name>.py`` (its ``read(run)``)."""
    return _module(BENCH_DIR / "metrics" / f"{name}.py", "metric").read
