"""Production mesh builders.

Importing this module never touches jax device state — meshes are built
inside functions only (the dry-run sets XLA_FLAGS before any jax init).

Axis semantics:
  pod   — data parallelism across pods (gradient all-reduce over DCI)
  data  — FSDP within a pod (params/optimizer reduce-scattered over ICI)
  model — tensor/expert parallelism within a pod
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices):
    """``jax.make_mesh`` with every axis Auto (the compiler propagates
    shardings from the annotated inputs)."""
    import jax
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes,
                     devices=jax.devices()[: int(np.prod(shape))])


def make_host_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Small mesh over however many (possibly fake) local devices exist —
    used by the mini-mesh integration tests."""
    import jax
    n = int(np.prod(shape))
    return make_mesh(shape, axes, devices=jax.devices()[:n])


def dp_size(mesh) -> int:
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            n *= mesh.shape[a]
    return n
