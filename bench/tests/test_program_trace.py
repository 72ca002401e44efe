"""The program's spans and named kernels in a trace recorded on a v5e
(``record_probe_train.py``: two ``Trainer.run_step`` calls of GPT-2 117M
cut to 2 layers, 8 rows at S = 256, each under ``bench.step`` inside
``bench.window``), and the readers that split them."""
import dataclasses
import json
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from bench.harness import program_trace as pt
from bench.harness import trace as t
from bench.harness.cell import metric_reader
from bench.reference.gpt2 import dims_from_config
from bench.tests.fixture import REPO
from bench.work import flash_attention as work
from bench.work import gpt2_step

DATA = Path(__file__).parent / "data"
PROBE = DATA / "probe_train.xplane.pb"
PHASES = ("train.plan", "train.batch", "train.launch", "train.wait",
          "train.observe")
ROWS, SEQ, LAYERS = 8, 256, 2


@pytest.fixture(scope="module")
def probe():
    return t.load(str(PROBE))


@pytest.fixture(scope="module")
def program():
    return pt.load(str(PROBE))


def _ctx(trace):
    cfg = json.loads((REPO / "bench" / "configs" / "gpt2-117m.json")
                     .read_text())
    peaks = json.loads((REPO / "bench" / "peaks.json").read_text())
    return SimpleNamespace(
        kind="train", trace=trace, peaks=peaks["TPU v5 lite"],
        dims=dims_from_config({**cfg, "n_layer": LAYERS}),
        cell=SimpleNamespace(dtype=jnp.dtype(jnp.float32),
                             arch={"work": gpt2_step}.__getitem__),
        steps=[(ROWS, SEQ)] * 2)


def _read(name, trace):
    return metric_reader(REPO, name)(_ctx(trace))


def test_program_spans_load_and_nest(probe, program):
    spans = program.spans
    names = [n for n, _, _ in spans]
    for name in ("train.step",) + PHASES:
        assert names.count(name) == 2, name
    benches = [sp for sp in probe.host_spans if sp[0] == "bench.step"]
    for (_, lo, hi), (_, blo, bhi) in zip(
            [sp for sp in spans if sp[0] == "train.step"], benches):
        assert blo <= lo < hi <= bhi
        inner = [sp for sp in spans if sp[0] in PHASES
                 and lo <= sp[1] and sp[2] <= hi]
        assert tuple(n for n, _, _ in inner) == PHASES


def test_kernels_carry_their_names(probe):
    count = {}
    for name, _, _ in probe.ops_in_window():
        base = name.rsplit(".", 1)[0]
        if probe.kinds[name] == "custom-call" and "flash" in base:
            count[base] = count.get(base, 0) + 1
    # per layer and step: the forward twice (remat full), each backward once
    per = 2 * LAYERS
    assert count == {"flash_attention_fwd": 2 * per,
                     "flash_attention_delta": per,
                     "flash_attention_dq": per,
                     "flash_attention_dkv": per}


def test_idle_by_bench_and_program_spans_sums_to_idle(probe, program):
    # the two launches lead their modules by nothing here: the clocks
    # pair, with no shift
    assert program.paired and probe.clock_shift_ns == 0
    idle = pt.idle_by_span(probe, program)
    assert sum(idle.values()) == pytest.approx(probe.window_s
                                               - probe.busy_s())
    assert set(idle) <= {"bench.window", "bench.step", "train.step"}.union(
        PHASES)
    assert idle["train.wait"] > 0


def test_idle_needs_the_device_clock_on_the_hosts(probe, program):
    unpaired = dataclasses.replace(program, paired=False)
    assert pt.idle_by_span(probe, unpaired) is None
    assert pt.idle_by_span(probe, pt.ProgramSpans([], paired=True)) is None


def test_roofline_readers_split_the_attention_seconds(probe):
    ctx = _ctx(probe)
    d, peaks = ctx.dims, ctx.peaks
    whole = LAYERS * 2 * work.least_seconds(ROWS, d.n_heads, SEQ,
                                            d.head_dim, d.head_dim, 4, peaks)
    fwd = LAYERS * 2 * work.least_seconds(ROWS, d.n_heads, SEQ, d.head_dim,
                                          d.head_dim, 4, peaks,
                                          backward=False)
    spent_fwd = 100.0 * fwd / _read("flash_attention_fwd_roofline", probe)
    spent_bwd = 100.0 * (whole - fwd) / _read("flash_attention_bwd_roofline",
                                              probe)
    spent = probe.kernel_s(("flash_attention",))
    assert spent_fwd + spent_bwd == pytest.approx(spent)
    assert _read("flash_attention_roofline", probe) == pytest.approx(
        100.0 * whole / spent)


def test_readers_find_nothing_in_a_trace_without_the_names():
    # the older probe: its kernels are named after the jitted wrapper, and
    # the program recorded no span
    old = t.load(str(DATA / "probe.xplane.pb"))
    assert _read("flash_attention_roofline", old) is not None
    assert _read("flash_attention_fwd_roofline", old) is None
    assert _read("flash_attention_bwd_roofline", old) is None
    program = pt.load(str(DATA / "probe.xplane.pb"))
    assert program.paired and program.spans == []
    assert pt.idle_by_span(old, program) is None
