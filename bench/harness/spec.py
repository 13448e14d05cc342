"""What one cell of the benchmark is, read from data.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Its configuration
is ``bench/configs/<config>.json`` (the file named in ``configs``), its
traffic mix ``bench/traffic/<traffic>.json``, and its per-layer metrics are
``bench/metrics/<metric>.py``. The configuration's ``"family"`` names its
architecture's module, ``bench/reference/<family>.py``: its widths, weight
layout, program tree and reference. Nothing here names a cell or a family:
a later cell, mix, metric or architecture is a new file and a new entry.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
REFERENCE_DIR = BENCH_DIR / "reference"


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    moves: str = ""
    layer: str = ""


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                 # the configuration file as it is run
    traffic_name: str
    traffic: dict                # the traffic mix's parameters
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)

    @property
    def engine(self) -> dict:
        return self.config["engine"]


def _reports(metric: dict, cell: str, default: bool) -> bool:
    listed = metric.get("workloads")
    return cell in listed if listed is not None else default


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Read cell ``name`` from ``BENCHMARK.json`` at ``root``; raises
    ``KeyError`` for a cell that is not there."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [Metric(m["name"], m["unit"], m["better"], m["source"])
           for m in bench["end_to_end"] if _reports(m, name, True)]
    e2e_names = {m.name for m in e2e}
    # a per-layer metric without a ``workloads`` list is reported in every
    # cell that reports the end-to-end metric it moves
    per_layer = [Metric(m["name"], m["unit"], m["better"], m["source"],
                        m["moves"], m["layer"])
                 for m in bench["per_layer"]
                 if _reports(m, name, m["moves"] in e2e_names)]
    return Cell(name, w["chips"], w["config"], config, w["traffic"], traffic,
                e2e, per_layer)


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(c: dict) -> ModuleType:
    """The module of configuration ``c``'s architecture family,
    ``bench/reference/<family>.py``. A configuration that names no family
    is refused: there is no default."""
    if "family" not in c:
        raise ValueError("the configuration names no 'family', the module "
                         f"of its architecture in {REFERENCE_DIR}")
    return load_family(c["family"], REFERENCE_DIR)


@functools.lru_cache(maxsize=None)
def load_family(name: str, directory: Path) -> ModuleType:
    """``<directory>/<name>.py``, loaded once per process, so that its
    jitted functions keep their compiled programs."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no family {name!r}: {path} is not there")
    spec = importlib.util.spec_from_file_location(
        "bench_family_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_peaks(kind: str, bench_dir: Path = BENCH_DIR) -> Dict[str, float]:
    """Peaks of one chip of ``device_kind`` ``kind``; an unknown kind is
    an error, never a default."""
    table = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in table or kind == "source":
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       f"bench/peaks.json (have "
                       f"{sorted(k for k in table if k != 'source')})")
    return table[kind]
