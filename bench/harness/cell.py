"""Everything the harness knows about a cell, found by name from files.

``BENCHMARK.json`` names the cell, its configuration and its traffic mix.
The rest is looked up by those names, so a later cell, mix, configuration
or metric is new files plus new entries, and no edit:

* ``<configuration file>`` named in ``BENCHMARK.json``: the model's sizes
  (the keys of its published ``config.json``, ``model_type`` among them),
  ``reduced``/``assumed``, and how the program runs it (``program``);
* the architecture's files, found by the configuration's ``model_type``
  (``ARCH_FILES``): ``bench/reference/<model_type>.py``, the plain
  reference, its ``Dims`` (``dims_from_config``) and its parameter tree
  (``weight_shapes``, ``weight_init``); ``bench/programs/<model_type>.py``,
  the program's model config for the file (``model_config``);
  ``bench/work/<model_type>_step.py``, the work of a step from its shapes
  (``train_step_flops``, ``attention_calls``);
* ``bench/traffic/<traffic>.json``: the training job;
* ``bench/limits/<cell>.json``: the limits of the numbers ``correct``
  compares, with the readings each was set from;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric;
* ``bench/peaks.json``: the chip's peaks by ``device_kind``.
"""
from __future__ import annotations

import functools
import hashlib
import importlib.util
import json
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

import jax.numpy as jnp

ARCH_FILES = {
    "reference": "bench/reference/{}.py",
    "programs": "bench/programs/{}.py",
    "work": "bench/work/{}_step.py",
}


class CellError(Exception):
    """The files do not describe a runnable cell."""


@functools.lru_cache(maxsize=None)
def _arch_module(path: Path) -> ModuleType:
    # once per file, so its classes and jitted functions stay the same
    # objects; registered under a name of its own, as dataclasses want
    name = "bench_arch_" + hashlib.sha256(str(path).encode()).hexdigest()[:16]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def arch_path(root: Path, part: str, model_type: str) -> Path:
    """Where ``part`` of the architecture ``model_type`` lives."""
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.-]*", str(model_type)):
        raise CellError(f"model_type {model_type!r} is not a plain name")
    return Path(root) / ARCH_FILES[part].format(model_type)


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

    def arch(self, part: str) -> ModuleType:
        """The module of ``part`` (a key of ``ARCH_FILES``) of the
        configuration's architecture."""
        return _arch_module(
            arch_path(self.root, part, self.config["model_type"]).resolve())

    @property
    def dims(self):
        return self.arch("reference").dims_from_config(self.config)

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
    if "model_type" not in config:
        raise CellError(f"config {w['config']!r} has no model_type")
    for part in ARCH_FILES:
        path = arch_path(root, part, config["model_type"])
        if not path.exists():
            raise CellError(f"model_type {config['model_type']!r} has no "
                            f"{part} file: looked for {path}")
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
