"""Pins of the SLW control plane's plans: the benchmark's SLW cell built
from its own argv, and ``predict_trajectory`` for every pacing kind, with
and without batch warmup.  The values were recorded before the control
plane was restructured; any change to them changes what the cell trains.
"""
import copy
import json
from pathlib import Path

import pytest

from repro.core.regulators import (ControllerState, StepTelemetry,
                                   build_stack, predict_trajectory)
from repro.launch.train import build_config, build_parser
from repro.optim.schedule import lr_at

SLW_TRAFFIC = (Path(__file__).resolve().parents[1]
               / "bench" / "traffic" / "train-slw.json")

SLW_LADDER = (8, 136, 264, 392, 520, 648, 776, 904, 1024)
SLW_SEQS = ((8,) * 9 + (136,) * 8 + (264,) * 8 + (392,) * 8 + (520,) * 8
            + (648,) * 8 + (776,) * 8 + (904,) * 7 + (1024,) * 8)
SLW_LRS = (
    2.86102294921875e-10, 7.352828979492186e-08, 1.4677047729492186e-07,
    2.2001266479492186e-07, 2.9325485229492187e-07, 3.6649703979492185e-07,
    4.3973922729492183e-07, 5.129814147949218e-07, 5.862236022949218e-07,
    6.594657897949218e-07, 1.9045829772949218e-06, 3.1497001647949216e-06,
    4.394817352294922e-06, 5.639934539794921e-06, 6.885051727294921e-06,
    8.130168914794922e-06, 9.37528610229492e-06, 1.062040328979492e-05,
    1.303739547729492e-05, 1.545438766479492e-05, 1.787137985229492e-05,
    2.028837203979492e-05, 2.270536422729492e-05, 2.512235641479492e-05,
    2.753934860229492e-05, 2.995634078979492e-05, 3.3545207977294916e-05,
    3.713407516479492e-05, 4.072294235229492e-05, 4.4311809539794917e-05,
    4.790067672729492e-05, 5.148954391479492e-05, 5.5078411102294917e-05,
    5.8667278289794914e-05, 6.342802047729491e-05, 6.818876266479492e-05,
    7.294950485229492e-05, 7.771024703979491e-05, 8.247098922729492e-05,
    8.723173141479492e-05, 9.199247360229491e-05, 9.675321578979491e-05,
    0.00010268583297729491, 0.00010861845016479491, 0.0001145510673522949,
    0.00012048368453979492, 0.0001264163017272949, 0.0001323489189147949,
    0.0001382815361022949, 0.0001442141532897949, 0.0001513186454772949,
    0.0001584231376647949, 0.00016552762985229492, 0.00017263212203979492,
    0.0001797366142272949, 0.0001868411064147949, 0.0001939455986022949,
    0.0002010500907897949, 0.00020932645797729492, 0.0002176028251647949,
    0.0002258791923522949, 0.0002341555595397949, 0.0002424319267272949,
    0.0002507082939147949, 0.0002589846611022949, 0.0002683596611022949,
    0.0002777346611022949, 0.0002871096611022949, 0.0002964846611022949,
    0.0003058596611022949, 0.0003152346611022949, 0.0003246096611022949,
)


def _slw_cell_stack():
    argv = json.loads(SLW_TRAFFIC.read_text())["argv"]
    args = build_parser().parse_args(argv)
    return build_stack(build_config(args), warmup_steps_hint=args.warmup)


def _walk(stack, n, step=0, tokens=0):
    """n plans on the calm telemetry the benchmark walks a ramp with."""
    plans = []
    for k in range(step, step + n):
        tele = StepTelemetry(step=k, tokens_seen=tokens, var_max=1.0,
                             var_l1=1.0, grad_norm=1.0)
        p = stack.plan(tele)
        plans.append((p.seq_len, p.batch_size, p.lr))
        stack.observe(tele, p.batch_size * p.seq_len)
        tokens += p.batch_size * p.seq_len
    return plans


def test_slw_cell_ramp_is_pinned():
    plans = _walk(_slw_cell_stack(), 72)
    assert tuple(sorted({s for s, _, _ in plans})) == SLW_LADDER
    assert plans == list(zip(SLW_SEQS, (32,) * 72, SLW_LRS))


def test_slw_cell_ramp_replays_after_restore():
    stack = _slw_cell_stack()
    cs0 = copy.deepcopy(stack.controller_state(0, 0, {}).to_host())
    first = _walk(stack, 72)
    stack.load_controller_state(ControllerState.from_host(copy.deepcopy(cs0)))
    cs = ControllerState.from_host(cs0)
    assert _walk(stack, 72, cs.step, cs.tokens_seen) == first


def test_slw_cell_bucket_starts_restore_their_bucket():
    """Each bucket's first-step state, taken as the benchmark takes it,
    plans that bucket again once loaded into a fresh stack."""
    stack = _slw_cell_stack()
    starts, tokens = [], 0
    for k in range(72):
        cs = copy.deepcopy(stack.controller_state(k, tokens, {}).to_host())
        (seq, rows, _), = _walk(stack, 1, k, tokens)
        if not starts or seq != starts[-1][0]:
            starts.append((seq, cs))
        tokens += rows * seq
    assert tuple(s for s, _ in starts) == SLW_LADDER
    for seq, host in starts:
        fresh = _slw_cell_stack()
        cs = ControllerState.from_host(host)
        fresh.load_controller_state(cs)
        assert _walk(fresh, 1, cs.step, cs.tokens_seen)[0][0] == seq


TRAJECTORIES = {
    ('linear', False):
        [(8, 16), (32, 16), (56, 16), (80, 16), (128, 16), (152, 16),
         (176, 16), (224, 16), (256, 16), (256, 16), (256, 16), (256, 16),
         (256, 16), (256, 16), (256, 16), (256, 16)],
    ('linear', True):
        [(8, 2), (32, 2), (56, 2), (80, 2), (128, 2), (152, 3), (176, 3),
         (224, 4), (256, 6), (256, 8), (256, 12), (256, 16), (256, 16),
         (256, 16), (256, 16), (256, 16)],
    ('root', False):
        [(8, 16), (80, 16), (128, 16), (152, 16), (176, 16), (200, 16),
         (200, 16), (224, 16), (256, 16), (256, 16), (256, 16), (256, 16),
         (256, 16), (256, 16), (256, 16), (256, 16)],
    ('root', True):
        [(8, 2), (80, 2), (128, 2), (152, 2), (176, 3), (200, 4),
         (200, 5), (224, 7), (256, 9), (256, 13), (256, 16), (256, 16),
         (256, 16), (256, 16), (256, 16), (256, 16)],
    ('two_stage', False):
        [(128, 16), (128, 16), (128, 16), (128, 16), (128, 16), (128, 16),
         (128, 16), (128, 16), (256, 16), (256, 16), (256, 16), (256, 16),
         (256, 16), (256, 16), (256, 16), (256, 16)],
    ('two_stage', True):
        [(128, 2), (128, 2), (128, 2), (128, 3), (128, 3), (128, 4),
         (128, 5), (128, 6), (256, 7), (256, 10), (256, 15), (256, 16),
         (256, 16), (256, 16), (256, 16), (256, 16)],
    ('variance_gated', False):
        [(8, 16), (32, 16), (56, 16), (80, 16), (104, 16), (128, 16),
         (152, 16), (176, 16), (200, 16), (224, 16), (248, 16), (256, 16),
         (256, 16), (256, 16), (256, 16), (256, 16)],
    ('variance_gated', True):
        [(8, 2), (32, 2), (56, 2), (80, 2), (104, 2), (128, 2), (152, 3),
         (176, 4), (200, 5), (224, 7), (248, 9), (256, 13), (256, 16),
         (256, 16), (256, 16), (256, 16)],
}


@pytest.mark.parametrize("pacing,batch_warmup", sorted(TRAJECTORIES))
def test_predict_trajectory_is_pinned(pacing, batch_warmup):
    argv = ["--batch", "16", "--seq", "256", "--steps", "40", "--slw",
            "--pacing", pacing, "--start-seq", "8", "--duration", "8",
            "--max-buckets", "12", "--warmup", "4"]
    args = build_parser().parse_args(
        argv + (["--batch-warmup"] if batch_warmup else []))
    tc = build_config(args)
    plans = predict_trajectory(tc, 16, warmup_steps_hint=args.warmup)
    assert [(p.seq_len, p.batch_size) for p in plans] == \
        TRAJECTORIES[(pacing, batch_warmup)]
    tokens = 0
    for k, p in enumerate(plans):
        assert p.lr == lr_at(tc.optimizer, k, tokens)
        tokens += p.seq_len * p.batch_size
