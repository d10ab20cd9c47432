"""Find a cell's configuration, traffic mix, limit and metric readers by
name.

``BENCHMARK.json`` at the repository root names the cells; each name
maps to a file of its own:

* configuration ``<c>``  -> the ``file`` its entry gives
  (``bench/configs/<c>.json``);
* family ``<f>`` (the configuration file's ``family`` key) ->
  ``bench/harness/families/<f>.py``: the model's weights, reference and
  operation counts (the contract is in ``harness/families/__init__.py``);
* traffic mix ``<t>``    -> ``bench/traffic/<t>.json``;
* cell ``<w>``           -> ``bench/limits/<w>.json`` (the correctness
  limit and the readings it was set from);
* per-layer metric ``<m>`` -> ``bench/metrics/<m>.py``, a module with
  ``read(run) -> float | None``.

A metric with a ``workloads`` key applies to the cells it lists; an
end-to-end metric without one to every cell, a per-layer metric without
one to every cell that reports the end-to-end metric it ``moves``.

A later cell, metric or model family is added by adding files and
entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

from harness.traffic import check_mix

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(ValueError):
    """A name the benchmark cannot resolve to its file."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limit: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    family: ModuleType             # harness/families/<config's family>.py


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(bm: dict, name: str, root: Path = ROOT,
                 bench: Optional[Path] = None) -> Cell:
    """The cell ``name`` of ``bm`` with every file it names loaded."""
    bench = bench or root / "bench"
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bm["configs"]}
    if w["config"] not in confs:
        raise SpecError(f"{name}: no configuration {w['config']!r}")
    config = load_json(root / confs[w["config"]]["file"])
    if "family" not in config:
        raise SpecError(f"{name}: configuration {w['config']!r} names no "
                        "family")
    family = load_family(config["family"], bench)
    traffic_path = bench / "traffic" / f"{w['traffic']}.json"
    if not traffic_path.is_file():
        raise SpecError(f"{name}: no traffic file {traffic_path}")
    limit_path = bench / "limits" / f"{name}.json"
    if not limit_path.is_file():
        raise SpecError(f"{name}: no limit file {limit_path}")
    traffic = load_json(traffic_path)
    try:
        check_mix(traffic)
    except ValueError as e:
        raise SpecError(f"{name}: {traffic_path}: {e}") from None
    e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in e2e}
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limit=load_json(limit_path), end_to_end=e2e,
                per_layer=[m for m in bm["per_layer"]
                           if _applies(m, name) and m["moves"] in moved],
                family=family)


def load_module(path: Path, prefix: str) -> ModuleType:
    """The module at ``path`` (names may hold dots and dashes, so it is
    loaded from its path, not imported by name)."""
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = BENCH) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = bench / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"no reader for metric {name!r} at {path}")
    return load_module(path, "bench_metric_").read


_FAMILIES: Dict[Path, ModuleType] = {}


def load_family(name: str, bench: Path = BENCH) -> ModuleType:
    """``bench/harness/families/<name>.py``, loaded once per process (its
    jitted reference is then traced once)."""
    path = bench / "harness" / "families" / f"{name}.py"
    if not (isinstance(name, str) and NAME.match(name) and path.is_file()):
        raise SpecError(f"no family {name!r} at {path}")
    path = path.resolve()
    if path not in _FAMILIES:
        _FAMILIES[path] = load_module(path, "bench_family_")
    return _FAMILIES[path]


def load_peaks(device_kind: str, bench: Path = BENCH) -> Dict[str, float]:
    """Published peaks of ``device_kind``.  An unknown device is an
    error: a default would put another chip's peak under this one's
    roofline shares."""
    table = load_json(bench / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"no peaks for device kind {device_kind!r}; "
                        f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]
