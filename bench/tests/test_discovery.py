"""A cell, its mix, configuration, limits and metrics are found from
files alone: adding one is new files and new entries, no edit."""
import json

import pytest

from bench.harness.cell import (ARCH_FILES, CellError, load_cell,
                                metric_reader, peaks_for)
from bench.tests.fixture import REPO, make_root


def test_fixture_cell_is_found_from_files(tmp_path):
    root = make_root(tmp_path)
    cell = load_cell(root, "tiny.tiny-slw")
    assert cell.kind == "train" and cell.config_name == "tiny"
    assert cell.dims.n_layers == 2 and cell.dims.d_ff == 512
    assert [m["name"] for m in cell.end_to_end] == ["train_tokens_per_s",
                                                     "setup_s"]
    # the fixture gives its cell every metric that the repository's
    # training cells report
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["per_layer"]
            if "gpt2-117m.train-slw" in m.get("workloads", [])}
    assert {m["name"] for m in cell.per_layer} == want
    for m in cell.per_layer:
        assert callable(metric_reader(root, m["name"]))


def test_new_cell_and_metric_need_only_new_files(tmp_path):
    root = make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    # a new mix, a new cell and a new metric: files plus entries
    (root / "bench" / "traffic" / "tiny-fixed.json").write_text(json.dumps(
        {"kind": "train", "rows": 2, "seq": 16, "warm_steps": 1,
         "ref_rows_per_block": 1,
         "argv": ["--batch", "2", "--seq", "16", "--steps", "100"]}))
    (root / "bench" / "limits" / "tiny.tiny-fixed.json").write_text(
        json.dumps({"loss_gap": {"limit": 1e-3}}))
    (root / "bench" / "metrics" / "answer.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["workloads"].append({"name": "tiny.tiny-fixed", "config": "tiny",
                               "traffic": "tiny-fixed", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "answer", "unit": "%",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "train_tokens_per_s",
                               "workloads": ["tiny.tiny-fixed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = load_cell(root, "tiny.tiny-fixed")
    assert cell.kind == "train" and cell.traffic["seq"] == 16
    assert [m["name"] for m in cell.per_layer] == ["answer"]
    assert metric_reader(root, "answer")(None) == 42.0


def test_unknown_cell_and_missing_files_are_errors(tmp_path):
    root = make_root(tmp_path)
    with pytest.raises(CellError):
        load_cell(root, "no-such-cell")
    (root / "bench" / "limits" / "tiny.tiny-slw.json").unlink()
    with pytest.raises(CellError):
        load_cell(root, "tiny.tiny-slw")


def test_peaks_are_keyed_by_device_kind():
    assert peaks_for(REPO, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(CellError):
        peaks_for(REPO, "cpu")


def test_every_real_cell_has_its_files():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = load_cell(REPO, w["name"])
        for m in cell.per_layer:
            metric_reader(REPO, m["name"])
        assert cell.dims.n_layers == cell.config["n_layer"]
        for part in ARCH_FILES:
            assert cell.arch(part).__file__.startswith(str(REPO))


def test_a_number_with_a_null_limit_is_not_compared():
    from bench.harness.compare import checks
    got = checks({"loss_gap": 1.0, "grad_gap": 2e-3},
                 {"loss_gap": {"limit": None}, "grad_gap": {"limit": 4e-3}})
    assert [(c.name, c.ok) for c in got] == [("grad_gap", True)]
    with pytest.raises(KeyError):
        checks({"update_gap": 1.0}, {})
    real = load_cell(REPO, "gpt2-117m.train-slw").limits
    assert real["loss_gap"]["limit"] is None
    assert real["grad_gap"]["lower"] < real["grad_gap"]["limit"] \
        < real["grad_gap"]["upper"]
    assert real["update_gap"]["lower"] < real["update_gap"]["limit"] \
        < real["update_gap"]["upper"]
