"""Mesh-agnostic pytree checkpoints: per-leaf .npy + JSON manifest.

Design goals (the fault-tolerance contract):

* **atomic** — written to ``<dir>/tmp.<step>``, fsynced, then renamed to
  ``<dir>/step_<step>``; a crash mid-write never corrupts the latest
  checkpoint.
* **mesh-agnostic** — leaves are stored as full logical arrays; restore
  applies whatever shardings the *new* mesh wants (elastic restart with a
  different device count is just a different `shardings` tree at load).
  At fleet scale the same manifest format extends to per-shard files keyed
  by (leaf, shard-index); single-process here, so leaves are whole.
* **self-validating** — the manifest records shape/dtype *and a crc32
  content checksum* per leaf plus a payload count; `latest_step` skips
  incomplete directories and `restore` raises
  :class:`CheckpointCorruption` on any shape/dtype/checksum mismatch or
  unreadable payload.  ``CheckpointManager.restore_latest`` turns that into
  automatic fallback: the corrupt directory is quarantined (renamed
  ``corrupt.step_*`` so no future restart trusts it, but the payload stays
  on disk for postmortems) and the previous ``step_*`` directory is tried.
* **host state included** — regulator state, loss-ratio tracker, data
  cursor, token counters ride along in the manifest's ``host`` dict, so a
  restart resumes the SLW schedule exactly.
* **fault-injectable** — the two rename-boundary crash points call into
  ``repro.distributed.fault_injection`` (no-ops unless a test/chaos run
  armed an injector), so crash-mid-checkpoint is a tested path, not an
  assumed one.
"""
from __future__ import annotations

import json
import os
import re
import shutil
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.distributed.fault_injection import checkpoint_crash_point


class CheckpointCorruption(ValueError):
    """A checkpoint directory failed validation (missing/unreadable payload,
    shape/dtype mismatch, or content-checksum mismatch)."""


def _flatten(tree: Any) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(_path_str(p) for p in path)
        out.append((key, leaf))
    return out


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


def save(directory: str, step: int, tree: Any,
         host_state: Optional[Dict] = None) -> str:
    """Atomically write checkpoint `step`. Returns the final path."""
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f"tmp.{step}")
    final = os.path.join(directory, f"step_{step:012d}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    leaves = _flatten(tree)
    manifest = {"step": step, "n_leaves": len(leaves), "leaves": {},
                "host": host_state or {}}
    for key, leaf in leaves:
        arr = np.asarray(jax.device_get(leaf))
        fname = re.sub(r"[^A-Za-z0-9_.-]", "_", key) + ".npy"
        np.save(os.path.join(tmp, fname), arr)
        manifest["leaves"][key] = {
            "file": fname, "shape": list(arr.shape), "dtype": str(arr.dtype),
            "crc32": zlib.crc32(np.ascontiguousarray(arr).tobytes())}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
        f.flush()
        os.fsync(f.fileno())
    checkpoint_crash_point("post_tmp", step)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    checkpoint_crash_point("post_rename", step)
    return final


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    best = None
    for name in os.listdir(directory):
        m = re.fullmatch(r"step_(\d+)", name)
        if not m:
            continue
        path = os.path.join(directory, name, "manifest.json")
        if not os.path.exists(path):
            continue  # incomplete
        step = int(m.group(1))
        best = step if best is None else max(best, step)
    return best


def available_steps(directory: str) -> List[int]:
    """Steps with a complete-looking checkpoint directory, newest first."""
    if not os.path.isdir(directory):
        return []
    out = []
    for name in os.listdir(directory):
        m = re.fullmatch(r"step_(\d+)", name)
        if m and os.path.exists(os.path.join(directory, name,
                                             "manifest.json")):
            out.append(int(m.group(1)))
    return sorted(out, reverse=True)


def quarantine(directory: str, step: int) -> str:
    """Rename a corrupt ``step_*`` directory to ``corrupt.step_*`` so no
    future restart trusts it (payload kept on disk for postmortems).
    Returns the quarantine path."""
    src = os.path.join(directory, f"step_{step:012d}")
    dst = os.path.join(directory, f"corrupt.step_{step:012d}")
    if os.path.exists(dst):
        shutil.rmtree(dst)
    os.rename(src, dst)
    return dst


def _legacy_opt_alias(key: str) -> Optional[str]:
    """Map a chain-format optimizer leaf key to its legacy monolithic
    location: pre-chain checkpoints stored the AdamW state flat under
    ``opt/`` (``opt/m/...``, ``opt/v/...``, ``opt/count``); the composable
    chain nests it under the ``adam`` transform slot.  Restoring an old
    checkpoint into a new trainer is therefore a key rename, not a copy."""
    m = re.fullmatch(r"opt/(?:shampoo/)?adam/((?:m|v)(?:/.*)?|count)", key)
    return f"opt/{m.group(1)}" if m else None


def restore(directory: str, step: int, like: Any,
            shardings: Optional[Any] = None) -> Tuple[Any, Dict]:
    """Restore into the structure of `like` (a pytree of arrays or
    ShapeDtypeStructs).  `shardings`, if given (same structure), device_puts
    each leaf with the *new* sharding — elastic re-mesh happens here.

    Every payload is validated against the manifest (shape, dtype, crc32
    content checksum when present — pre-hardening manifests lack it and
    still restore); any mismatch raises :class:`CheckpointCorruption`.
    Legacy ``{"m","v","count"}`` optimizer payloads are transparently
    migrated into the chain format via :func:`_legacy_opt_alias`.
    """
    path = os.path.join(directory, f"step_{step:012d}")
    try:
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise CheckpointCorruption(f"unreadable manifest in {path}: {e}")
    keys = {}
    for k, _ in _flatten(like):
        if k in manifest["leaves"]:
            keys[k] = k
            continue
        alias = _legacy_opt_alias(k)
        if alias is not None and alias in manifest["leaves"]:
            keys[k] = alias
        else:
            keys[k] = k  # reported missing below
    missing = [k for k, src in keys.items() if src not in manifest["leaves"]]
    if missing:
        raise ValueError(f"checkpoint missing leaves: {missing[:5]} ...")
    arrays = {}
    for key, src in keys.items():
        meta = manifest["leaves"][src]
        fpath = os.path.join(path, meta["file"])
        try:
            arr = np.load(fpath)
        except Exception as e:  # noqa: BLE001 — any load failure = corrupt
            raise CheckpointCorruption(f"unreadable payload {fpath}: {e}")
        if list(arr.shape) != list(meta["shape"]) \
                or str(arr.dtype) != meta["dtype"]:
            raise CheckpointCorruption(
                f"{fpath}: shape/dtype {arr.shape}/{arr.dtype} != manifest "
                f"{tuple(meta['shape'])}/{meta['dtype']}")
        if "crc32" in meta:
            crc = zlib.crc32(np.ascontiguousarray(arr).tobytes())
            if crc != meta["crc32"]:
                raise CheckpointCorruption(
                    f"{fpath}: crc32 {crc:#010x} != manifest "
                    f"{meta['crc32']:#010x}")
        arrays[key] = arr
    flat_like, treedef = jax.tree_util.tree_flatten(like)
    flat_sh = (jax.tree_util.tree_flatten(shardings)[0]
               if shardings is not None else [None] * len(flat_like))
    out = []
    for (key, _), sh in zip(_flatten(like), flat_sh):
        arr = arrays[key]
        out.append(jax.device_put(arr, sh) if sh is not None else arr)
    return jax.tree_util.tree_unflatten(treedef, out), manifest["host"]


def migrate_host_state(host: Dict) -> Dict:
    """Upgrade a legacy host dict to the unified controller format.

    Pre-regulator checkpoints carried per-object payloads
    (``{"curriculum": ..., "tracker": ...}``); the control plane now
    checkpoints one ``controller`` dict (see core.regulators.ControllerState).
    Legacy curriculum state maps onto the ``seqlen`` regulator's slot, and
    its step becomes the controller's when the dict has no ``step`` of its
    own.
    A host dict carrying a legacy monolithic ``{"m","v","count"}`` opt
    state (in-memory snapshots, ring payloads) is upgraded into the chain
    format (``{"adam": {...}, ...}``); the on-disk equivalent happens
    leaf-wise in :func:`restore` via :func:`_legacy_opt_alias`.
    """
    if isinstance(host.get("opt"), dict):
        from repro.optim.transforms import migrate_opt_state
        new_opt = migrate_opt_state(host["opt"])
        if new_opt is not host["opt"]:
            host = dict(host)
            host["opt"] = new_opt
    if "controller" in host:  # already new-format: pass through untouched
        return host
    out = dict(host)
    regs = {}
    if "curriculum" in host:
        regs["seqlen"] = host["curriculum"]
    out["controller"] = {
        # the curriculum kept its own step; it is the run's step when the
        # dict has none beside it
        "step": host.get("step", host.get("curriculum", {}).get("step", 0)),
        "tokens_seen": host.get("tokens_seen", 0),
        "regulators": regs,
        "tracker": host.get("tracker", {}),
    }
    return out


class CheckpointManager:
    """keep-N garbage collection + convenience wrappers."""

    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        # (step, quarantine path, reason) for every corrupt dir sidelined
        self.quarantined: List[Tuple[int, str, str]] = []

    def save(self, step: int, tree: Any, host_state: Optional[Dict] = None):
        path = save(self.directory, step, tree, host_state)
        self._gc()
        return path

    def latest_step(self) -> Optional[int]:
        return latest_step(self.directory)

    def restore_latest(self, like: Any, shardings: Optional[Any] = None):
        """Restore the newest checkpoint that passes validation.

        A corrupt newest checkpoint (bitflip, torn write) is quarantined —
        renamed ``corrupt.step_*``, payload kept for postmortems — and the
        previous ``step_*`` directory is tried, until one validates or none
        are left (then the None-tuple, same as an empty directory: the
        caller cold-starts)."""
        for step in available_steps(self.directory):
            try:
                tree, host = restore(self.directory, step, like, shardings)
                return step, tree, host
            except CheckpointCorruption as e:
                self.quarantined.append(
                    (step, quarantine(self.directory, step), str(e)))
        return None, None, None

    def _gc(self):
        steps = sorted(
            int(m.group(1)) for m in
            (re.fullmatch(r"step_(\d+)", n)
             for n in os.listdir(self.directory)) if m)
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:012d}"),
                          ignore_errors=True)
