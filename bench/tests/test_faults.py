"""A run with the timed path broken underneath comes out not correct.

Each test drives the rest of a run (the look for a chip skipped) at a
small size on the CPU, once for each fault a training cell can have: a
step that returns its state unchanged, or that leaves out half of the
batch and takes the mean over the rest, in every bucket or only in the
longest.  (The cells run on one chip: there is no exchange between chips
to leave out.)  A clean run of the same cell is correct, and its check
covers every bucket of the ramp."""
import time

import pytest

from bench.control import half_batch, unchanged_state
from bench.harness.runner import run_cell
from bench.tests.fixture import make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("faults"))


def _run(root, cell, faults=()):
    return run_cell(root, cell, 2**31 + 99, 1.0, False,
                    time.perf_counter(), require_chip=False,
                    faults=faults)


def half_batch_at_longest(trainer):
    """Fault: only steps at the full sequence length leave out half of
    the batch, as a kernel wrong only past its first block would."""
    full = trainer.tc.seq_len
    step = trainer.step_fn

    def run(state, batch, *a, **kw):
        if batch["tokens"].shape[1] == full:
            batch = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return step(state, batch, *a, **kw)

    trainer.step_fn = run


def test_clean_training_run_is_correct(root):
    res = _run(root, "tiny.tiny-slw")
    assert res["line"]["correct"] is True
    assert set(res["line"]["checks"]) == {"loss_gap", "grad_gap",
                                          "update_gap"}
    # one checked run per bucket of the ramp: 8 up to the full 32
    seqs = [r["seq"] for r in res["diag"]["runs"]]
    assert seqs[0] == 8 and sorted(seqs[1:]) == seqs[1:]
    assert seqs[-1] == 32 and len(set(seqs)) == len(seqs) >= 3


@pytest.mark.parametrize(
    "fault", [unchanged_state, half_batch, half_batch_at_longest],
    ids=["unchanged_state", "half_batch", "half_batch_at_longest"])
def test_broken_training_step_is_not_correct(root, fault):
    line = _run(root, "tiny.tiny-slw", (fault,))["line"]
    assert line["correct"] is False
