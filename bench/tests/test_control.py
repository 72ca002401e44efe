"""The controls come out not correct against the cell's limits, at a size
a test run holds: the reference with int8 matrix products or in bfloat16
put in the training program's place, and the program with half of each
batch left out."""
import pytest

from bench import control
from bench.harness.cell import load_cell
from bench.harness.compare import checks
from bench.tests.fixture import make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("control"))


def test_training_control_fails_and_the_program_passes(root):
    cell = load_cell(root, "tiny.tiny-slw")
    got = control.train_readings(cell, 12345)

    def ok(what):
        return all(c.ok for c in checks(got[what]["worst"], cell.limits))

    assert ok("program")
    assert not ok("control_int8")
    assert not ok("control_bfloat16")
    assert not ok("half_batch")
