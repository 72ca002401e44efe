"""Everything the harness knows about a cell, found by name from files.

``BENCHMARK.json`` names the cell, its configuration and its traffic mix.
The rest is looked up by those names, so a later cell, mix, configuration
or metric is new files plus new entries, and no edit:

* ``<configuration file>`` named in ``BENCHMARK.json``: the model's sizes
  (GPT-2 ``config.json`` keys), ``reduced``/``assumed``, and how the
  program runs it (``program``);
* ``bench/traffic/<traffic>.json``: the training job;
* ``bench/limits/<cell>.json``: the limits of the numbers ``correct``
  compares, with the readings each was set from;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric;
* ``bench/peaks.json``: the chip's peaks by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import jax.numpy as jnp

from bench.reference.gpt2 import Dims, dims_from_config


class CellError(Exception):
    """The files do not describe a runnable cell."""


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    overrides: Dict[str, Any] = field(default_factory=dict)

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    @property
    def dims(self) -> Dims:
        return dims_from_config(self.config)

    @property
    def program(self) -> Dict[str, Any]:
        return {**self.config["program"], **self.overrides}

    @property
    def dtype(self):
        return jnp.dtype(self.program["dtype"])


def _load_json(path: Path) -> Dict[str, Any]:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise CellError(f"missing file {path}") from None


def _applies(metric: Dict[str, Any], cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def load_cell(root: Path, name: str, overrides: Optional[dict] = None
              ) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` and its files."""
    root = Path(root)
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise CellError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _load_json(root / configs[w["config"]]["file"])
    traffic = _load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(root / "bench" / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(root=root, name=name, chips=int(w["chips"]),
                config_name=w["config"], config=config,
                traffic_name=w["traffic"], traffic=traffic, limits=limits,
                end_to_end=e2e, per_layer=per_layer,
                overrides=dict(overrides or {}))


def peaks_for(root: Path, device_kind: str) -> Dict[str, Any]:
    """The chip's published peaks; an unknown kind is an error."""
    table = _load_json(Path(root) / "bench" / "peaks.json")
    if device_kind not in table:
        raise CellError(f"no peaks for device kind {device_kind!r} in "
                        f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def metric_reader(root: Path, name: str) -> Callable[[Any], Optional[float]]:
    """``read(ctx)`` of ``bench/metrics/<name>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        raise CellError(f"no reader {path} for metric {name!r}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
