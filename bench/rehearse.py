#!/usr/bin/env python3
"""Compile each cell's programs for a described TPU v5e, with no chip.

  JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]

For each training cell: the program's train step at the cell's rows and
full sequence length.  Prints the compiler's ``memory_analysis()`` for
each, so row counts are fixed before any chip time; nothing runs.  It describes a ``v5e:2x2`` topology and compiles for
its first chip; code that asks whether it runs on a TPU is told yes, so
the Pallas kernels are compiled as they would be there.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _shapes(tree, sharding):
    import jax
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def rehearse_train(cell, sharding):
    import jax
    import jax.numpy as jnp
    from bench.harness import program
    from repro.configs.base import OptimizerConfig
    from repro.launch import steps as steps_lib
    from repro.models import model_zoo

    cfg = program.model_config(cell)
    model = model_zoo.build_model(cfg, dtype=jnp.float32,
                                  remat=cell.program["remat"])
    opt = OptimizerConfig()
    state = _shapes(steps_lib.abstract_train_state(cfg, opt), sharding)
    rows, seq = cell.traffic["rows"], cell.traffic["seq"]
    tok = jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=sharding)
    scalar = jax.ShapeDtypeStruct((), jnp.float32, sharding=sharding)
    step = jax.jit(steps_lib.make_train_step(model, opt),
                   donate_argnums=(0,))
    compiled = step.lower(state, {"tokens": tok, "labels": tok}, scalar,
                          scalar).compile()
    return {f"train step {rows}x{seq}": compiled}


def main(argv):
    import json
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import repro.kernels
    import repro.models.attention
    from bench.harness.cell import load_cell

    # the described chip is a TPU; the process's own backend is the CPU
    repro.kernels.on_tpu = lambda: True
    repro.models.attention.on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = argv or [w["name"] for w in bench["workloads"]]
    for name in names:
        cell = load_cell(ROOT, name)
        for what, compiled in rehearse_train(cell, one_chip).items():
            m = compiled.memory_analysis()
            kernel = "tpu_custom_call" in compiled.as_text()
            print(f"{name}: {what}: arguments "
                  f"{m.argument_size_in_bytes / 1e9:.3f} GB, outputs "
                  f"{m.output_size_in_bytes / 1e9:.3f} GB, temporaries "
                  f"{m.temp_size_in_bytes / 1e9:.3f} GB, aliased "
                  f"{m.alias_size_in_bytes / 1e9:.3f} GB; Pallas kernel "
                  f"{'present' if kernel else 'ABSENT'}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
