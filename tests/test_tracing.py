"""The trainer's host spans, as a profiler trace records them."""
import glob
import os

import jax
import pytest

from repro.configs import get_arch, reduced
from repro.configs.base import OptimizerConfig, SLWConfig, TrainConfig
from repro.launch.train import Trainer

PHASES = ("train.plan", "train.batch", "train.launch", "train.wait",
          "train.observe")


def _host_spans(logdir):
    """(name, start_ns, end_ns) of every ``train.*`` event on the host."""
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return sorted(((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                   for plane in data.planes if plane.name.startswith("/host")
                   for line in plane.lines for ev in line.events
                   if ev.name.startswith("train.")), key=lambda sp: sp[1])


def _tiny_slw():
    return TrainConfig(
        model=reduced(get_arch("gpt2-117m").model).replace(vocab_size=256),
        optimizer=OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=8),
        slw=SLWConfig(enabled=True, start_seq_len=8, duration_steps=4,
                      max_buckets=2),
        seq_len=32, global_batch=4, remat="none", eval_interval=0,
        checkpoint_interval=0)


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    tr = Trainer(_tiny_slw(), quiet=True)
    assert "seqlen" in tr.stack  # SLW plans the steps
    logdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(logdir):
        for _ in range(2):
            tr.run_step()
    return _host_spans(logdir)


def test_each_span_once_per_step(spans):
    names = [n for n, _, _ in spans]
    for name in ("train.step",) + PHASES:
        assert names.count(name) == 2, name


def test_phases_nest_in_the_step_in_order(spans):
    steps = [sp for sp in spans if sp[0] == "train.step"]
    for _, lo, hi in steps:
        inner = [sp for sp in spans if sp[0] != "train.step"
                 and lo <= sp[1] and sp[2] <= hi]
        assert tuple(n for n, _, _ in inner) == PHASES
        for (_, _, end), (_, start, _) in zip(inner, inner[1:]):
            assert end <= start
