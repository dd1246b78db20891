"""Discovery by name: everything a cell needs is found from
``BENCHMARK.json`` and files named after its entries.

- configuration ``<c>``: ``bench/configs/<c>.json`` (the ``file`` key),
  whose ``reference`` names its plain reference ``bench/references/<r>.py``
  (``init`` for the weights, ``fwd_flops`` for the layers' FLOPs, and
  ``hidden``, ``logits``, ``values`` for the reference's forward) and
  whose ``models`` entries are ``ModelConfig`` fields
  (``bench/lib/models.py``);
- traffic mix ``<t>``: ``bench/traffic/<t>.json``, whose ``kind`` names
  the module ``bench/kinds/<kind>.py``;
- per-layer metric ``<m>``: ``bench/metrics/<m>.py`` with ``read(rec)``;
- cell ``<w>``: its limits in ``bench/cells/<w>.json``.

A later change adds a cell, a configuration, a mix or a metric by adding
such files and entries; nothing here is edited for it.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class SpecError(Exception):
    """A name in BENCHMARK.json resolves to no file, or a file is malformed."""


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: Optional[str] = None
    moves: Optional[str] = None
    bound: Optional[float] = None
    workloads: Optional[tuple] = None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]          # the configuration file's contents
    traffic: Dict[str, Any]         # the traffic file's contents
    limits: Dict[str, Any]          # bench/cells/<name>.json
    end_to_end: List[Metric]        # reported with --trace 0
    per_layer: List[Metric]         # reported with --trace 1
    root: Path = ROOT

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _read_json(path: Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path}: {e}") from None


def _metric(d: Dict[str, Any]) -> Metric:
    wl = d.get("workloads")
    return Metric(name=d["name"], unit=d["unit"], better=d["better"],
                  source=d["source"], layer=d.get("layer"),
                  moves=d.get("moves"), bound=d.get("bound"),
                  workloads=tuple(wl) if wl is not None else None)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _read_json(Path(root) / "BENCHMARK.json")


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve workload ``name`` into its configuration, traffic, limits
    and the metrics it reports."""
    root = Path(root)
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    if "kind" not in traffic:
        raise SpecError(f"traffic {w['traffic']!r} names no kind")
    limits = _read_json(root / "bench" / "cells" / f"{name}.json")

    def applies(m: Metric, reported: set) -> bool:
        if m.workloads is not None:
            return name in m.workloads
        return m.moves is None or m.moves in reported

    e2e = [_metric(m) for m in bench["end_to_end"]]
    e2e = [m for m in e2e if m.workloads is None or name in m.workloads]
    reported = {m.name for m in e2e}
    per_layer = [m for m in map(_metric, bench["per_layer"])
                 if applies(m, reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer, root=root)


def _load_module(path: Path, modname: str):
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(kind: str, root: Path = ROOT):
    """The module of a traffic kind: ``bench/kinds/<kind>.py``."""
    return _load_module(Path(root) / "bench" / "kinds" / f"{kind}.py",
                        f"bench_kind_{kind}")


def load_reference(name: str, root: Path = ROOT):
    """A configuration's plain reference: ``bench/references/<name>.py``."""
    return _load_module(Path(root) / "bench" / "references" / f"{name}.py",
                        f"bench_reference_{name}")


def load_metric_reader(name: str, root: Path = ROOT):
    """The reader of per-layer metric ``name``: ``bench/metrics/<name>.py``,
    whose ``read(rec)`` returns a number or None (nothing to read)."""
    mod = _load_module(Path(root) / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_")
                       .replace("-", "_"))
    return mod.read
