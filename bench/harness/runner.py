"""One run of one cell: device check, set-up, window, check, result line."""
from __future__ import annotations

import json
import math
import os
import sys
from pathlib import Path
from types import SimpleNamespace
from typing import Any, Callable, Dict, Optional, Sequence

from bench.harness import compare
from bench.harness import trace as trace_mod
from bench.harness.cell import Cell, load_cell, metric_reader, peaks_for


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def configure_compile_cache(root: Path) -> str:
    """JAX's persistent cache at ``.jax_cache/`` inside the checkout, at a
    fixed path, whatever the environment says; every program is kept."""
    path = str(Path(root) / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    from repro.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(cell: Cell, require_chip: bool) -> Dict[str, Any]:
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name!r} needs {cell.chips} TPU chip(s); "
                     f"JAX found {len(devs)} {devs[0].platform!r} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": cell.chips}


def per_layer(cell: Cell, out: Dict[str, Any], peaks) -> Dict[str, Any]:
    """Every per-layer metric of the cell whose reader finds something."""
    ctx = SimpleNamespace(cell=cell, dims=cell.dims, peaks=peaks,
                          trace=out["trace"], **out["ctx"])
    metrics = {}
    for m in cell.per_layer:
        value = metric_reader(cell.root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def run_cell(root: Path, name: str, seed: int, seconds: float,
             tracing: bool, t_start: float, *, require_chip: bool = True,
             faults: Sequence[Callable] = (),
             overrides: Optional[dict] = None) -> Dict[str, Any]:
    """The result line of one run, as a dict.  ``require_chip=False`` and
    ``faults`` are for tests: they skip the look for a chip and break the
    timed path underneath."""
    cell = load_cell(root, name, overrides)
    device = device_info(cell, require_chip)
    if device["platform"] == "tpu":
        configure_compile_cache(root)
    from bench.harness.spans import CompileClock
    clock = CompileClock()
    try:
        peaks = peaks_for(root, device["kind"])
    except Exception:
        if require_chip:
            raise
        peaks = None
    if cell.kind != "train":
        raise ValueError(f"unknown traffic kind {cell.kind!r}")
    from bench.harness import train as driver
    out = driver.run(cell, seed, seconds, tracing, t_start, faults)
    checks = compare.checks(out["numbers"], cell.limits)

    if tracing:
        metrics = per_layer(cell, out, peaks)
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": out["setup_s"], "unit": "s"}
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    line: Dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": out["attempted"],
        "failed": out["failed"],
        "metrics": metrics,
        "device": device,
    }
    tr = out["trace"]
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        line["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                             "idle_gaps": trace_mod.idle_by_span(tr)}
    diag = {"compiles_in_window": clock.compiles_between(*out["window"]),
            "compile_s": clock.seconds, "cache_hits": clock.cache_hits,
            "cache_misses": clock.cache_misses,
            "window_s": out["window_s"], **out.get("checked", {})}
    line["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                               else None, "limit": c.limit}
                      for c in checks}
    return {"line": line, "diag": diag}


def main(root: Path, argv: Sequence[str], t_start: float) -> int:
    import argparse
    p = argparse.ArgumentParser(
        prog="bench/run.py",
        description="One run of one benchmark cell on the chip.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    if args.seed < 0:
        p.error("--seed must be >= 0")
    try:
        res = run_cell(root, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 1
    line = res["line"]
    print("diag " + json.dumps(res["diag"]), file=sys.stderr)
    for name, c in line["checks"].items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
