"""Resolve a cell of ``BENCHMARK.json`` to its files, by name.

A cell names a configuration (``configs[].file``) and a traffic mix
(``bench/traffic/<traffic>.json``); its limits for ``correct`` are in
``bench/limits/<cell>.json`` and each per-layer metric's reader is
``bench/metrics/<metric>.py``.  The configuration names its program
(``bench/programs/<program>.py``, the only way to the model), its plain
reference (``bench/refs/<reference>.py``), and the traffic its generator
(``bench/gen/<generator>.py``).  Every file is looked up under the root of the
``BENCHMARK.json`` that names the cell.  Adding a cell, a configuration, a
program, a mix or a metric adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple
    per_layer: tuple
    root: Path


def manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str, reported=None) -> bool:
    """A metric with ``workloads`` applies to the cells it lists; a per-layer
    metric without them, to every cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(name: str, root: Path = ROOT) -> Cell:
    m = manifest(root)
    work = {w["name"]: w for w in m["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in m["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    if "program" not in config:
        raise KeyError(f"configuration {conf['name']!r} ({conf['file']}) names no \"program\": give the "
                       f"name of its module in bench/programs/")
    end_to_end = tuple(e for e in m["end_to_end"] if _applies(e, name))
    reported = {e["name"] for e in end_to_end}
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((root / "bench" / "limits" / f"{name}.json").read_text()),
        end_to_end=end_to_end,
        per_layer=tuple(p for p in m["per_layer"] if _applies(p, name, reported)),
        root=root,
    )


def load_module(kind: str, name: str, root: Path = ROOT):
    """``bench/<kind>/<name>.py`` as a module (programs, metric readers,
    references, generators), loaded once per file."""
    return _load(kind, name, (root / "bench" / kind / f"{name}.py").resolve())


@functools.cache
def _load(kind: str, name: str, path: Path):
    module = f"bench_{kind}_" + "".join(ch if ch.isalnum() else "_" for ch in name)
    spec = importlib.util.spec_from_file_location(module, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program(cell: Cell):
    """The module of the cell's program."""
    return load_module("programs", cell.config["program"], cell.root)
