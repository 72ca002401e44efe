"""An architecture is a set of files found by the configuration's
``model_type``: a configuration of another architecture needs new files
and appended entries, and no edit of the harness or the metric readers.
Moving GPT-2's files behind that lookup leaves its seeded weights as they
were, bit for bit."""
import hashlib
import json
import shutil
import time

import jax
import numpy as np
import pytest

from bench.harness import weights
from bench.harness.cell import CellError, load_cell
from bench.harness.runner import run_cell
from bench.tests.fixture import LIMITS, TINY, make_root

# sha256 of each leaf of the tiny cell's weights from seed 2**31 + 99,
# recorded before GPT-2's tree moved into bench/reference/gpt2.py
PINNED = {
    "embed": "a8f8ccc363fc22ef",
    "final_norm/bias": "eb000cd61346b88f",
    "final_norm/scale": "066811a5a19f3a12",
    "layers/attn/wk": "ca8f24bca68a5402",
    "layers/attn/wo": "430a6419b5164b23",
    "layers/attn/wq": "12405ce340bc0604",
    "layers/attn/wv": "e37f031b979823b9",
    "layers/ln1/bias": "da9db2e13525e734",
    "layers/ln1/scale": "36b790238864e97f",
    "layers/ln2/bias": "7bca17fc64d086db",
    "layers/ln2/scale": "10eb5e6cc4ff332b",
    "layers/mlp/b_down": "dd231ef410b91c27",
    "layers/mlp/b_up": "db11eb791ce65beb",
    "layers/mlp/w_down": "74256ae9c1150a39",
    "layers/mlp/w_up": "85b61ca8cd480b3d",
    "pos_embed": "b6766f615bca2c41",
}


def _add_cell(root, config_name, config, traffic="tiny-slw"):
    """Appends a configuration and its cell to the tree's entries."""
    (root / "bench" / "configs" / f"{config_name}.json").write_text(
        json.dumps(config))
    cell = f"{config_name}.{traffic}"
    (root / "bench" / "limits" / f"{cell}.json").write_text(
        json.dumps(LIMITS[f"tiny.{traffic}"]))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": config_name, "source": config["source"],
                             "file": f"bench/configs/{config_name}.json",
                             "reduced": config["reduced"], "why": "test"})
    bench["workloads"].append({"name": cell, "config": config_name,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


def test_seeded_weights_are_pinned(tmp_path):
    cell = load_cell(make_root(tmp_path), "tiny.tiny-slw")
    leaves, _ = jax.tree_util.tree_flatten_with_path(
        weights.make(2**31 + 99, cell))
    got = {"/".join(str(k.key) for k in p):
           hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]
           for p, x in leaves}
    assert got == PINNED
    # and in the order the one split of the seed's key hands out
    assert list(got) == sorted(PINNED)


def test_an_architecture_from_new_files_alone_runs_to_correct(tmp_path):
    root = make_root(tmp_path)
    # a new model_type whose files exist only under this root
    for src, dst in (("reference/gpt2.py", "reference/gpt2copy.py"),
                     ("programs/gpt2.py", "programs/gpt2copy.py"),
                     ("work/gpt2_step.py", "work/gpt2copy_step.py")):
        shutil.copy(root / "bench" / src, root / "bench" / dst)
    name = _add_cell(root, "tinycopy", dict(TINY, model_type="gpt2copy"))
    cell = load_cell(root, name)
    for part in ("reference", "programs", "work"):
        assert cell.arch(part).__file__.startswith(str(root))
        assert "gpt2copy" in cell.arch(part).__file__
    res = run_cell(root, name, 2**31 + 7, 1.0, False, time.perf_counter(),
                   require_chip=False)
    assert res["line"]["correct"] is True
    assert set(res["line"]["checks"]) == {"loss_gap", "grad_gap",
                                          "update_gap"}


@pytest.mark.parametrize("model_type", ["deepseek_v3", "../gpt2", None])
def test_an_unknown_model_type_is_an_error_that_names_the_path(
        tmp_path, model_type):
    root = make_root(tmp_path)
    config = dict(TINY, model_type=model_type)
    if model_type is None:
        del config["model_type"]
    name = _add_cell(root, "other", config)
    with pytest.raises(CellError) as e:
        load_cell(root, name)
    if model_type == "deepseek_v3":
        assert "deepseek_v3" in str(e.value)
        assert str(root / "bench" / "reference" / "deepseek_v3.py") \
            in str(e.value)
