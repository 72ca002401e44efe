"""The operation and byte counts of bench/work against hand-worked numbers,
for the benchmark's configuration and for GPT-2 XL's widths at 12 layers."""
import json

import pytest

from bench.reference.gpt2 import dims_from_config
from bench.tests.fixture import REPO
from bench.work import flash_attention, gpt2_step


def dims(name):
    return dims_from_config(json.loads(
        (REPO / "bench" / "configs" / f"{name}.json").read_text()))


def test_matmul_params_by_hand():
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 768 x 50257
    assert gpt2_step.matmul_params(dims("gpt2-117m")) == 123_532_032
    # 12 x (4 x 1600^2 + 2 x 1600 x 6400) + 1600 x 50257
    assert gpt2_step.matmul_params(dims("gpt2-1.5b-l12")) == 449_051_200


def test_train_step_flops_by_hand():
    d = dims("gpt2-117m")
    # forward of one 1024-token row: 2 x 123,532,032 x 1024 for the
    # matrices, 12 layers x 2 products x 2 x (1024^2 / 2) x 768 for
    # attention; training is three forward passes
    fwd = 2 * 123_532_032 * 1024 + 12 * 2 * 2 * (1024 ** 2 // 2) * 768
    assert fwd == 272_320_954_368
    assert gpt2_step.train_step_flops(d, 1, 1024) == 3 * fwd
    assert gpt2_step.train_step_flops(d, 32, 1024) == 32 * 3 * fwd
    x = dims("gpt2-1.5b-l12")
    fwd_x = 2 * 449_051_200 * 1024 + 12 * 2 * 2 * (1024 ** 2 // 2) * 1600
    assert gpt2_step.train_step_flops(x, 8, 1024) == 8 * 3 * fwd_x


def test_flash_attention_work_by_hand():
    # b=1, 12 heads, S=1024, D=64: the causal half is 12 x 524,288 x 64
    half = 12 * 524_288 * 64
    f = flash_attention.flops(1, 12, 1024, 64, 64)
    assert f == {"fwd": 4 * half, "bwd": 8 * half}
    m = flash_attention.bytes_moved(1, 12, 1024, 64, 64, 4)
    tile = 12 * 1024 * 64 * 4
    assert m == {"fwd": 4 * tile + 12 * 1024 * 4,
                 "bwd": 8 * tile + 2 * 12 * 1024 * 4}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # float32 at S=1024: S/8 = 128 operations per byte, under the chip's
    # 197e12 / 819e9 = 240, so the forward is bound by bandwidth
    t = flash_attention.least_seconds(1, 12, 1024, 64, 64, 4, peaks,
                                      backward=False)
    assert t == pytest.approx(m["fwd"] / 819e9)
    assert m["fwd"] / 819e9 > 4 * half / 197e12
    # bfloat16 at S=4096: 4096/4 = 1024 per byte, bound by compute
    f4 = flash_attention.flops(1, 12, 4096, 64, 64)["fwd"]
    assert flash_attention.least_seconds(
        1, 12, 4096, 64, 64, 2, peaks, backward=False) == \
        pytest.approx(f4 / 197e12)


def test_flash_attention_work_with_wider_queries_and_keys():
    # latent attention's shapes: d_qk 192 (128 + 64 rotary), d_v 128; 16
    # heads, S=1024; the causal half is 16 x 524,288 entries per head
    cells = 16 * 524_288
    f = flash_attention.flops(1, 16, 1024, 192, 128)
    # forward: scores over 192, probabilities x values over 128;
    # backward: dV and dP over 128, dQ and dK over 192
    assert f == {"fwd": 2 * cells * 192 + 2 * cells * 128,
                 "bwd": 4 * cells * 192 + 4 * cells * 128}
    m = flash_attention.bytes_moved(1, 16, 1024, 192, 128, 2)
    qk, v, rows = 16 * 1024 * 192 * 2, 16 * 1024 * 128 * 2, 16 * 1024 * 4
    assert m == {"fwd": 2 * qk + 2 * v + rows,
                 "bwd": 4 * qk + 4 * v + 2 * rows}


def test_gpt2_attention_calls_and_their_least_time():
    d = dims("gpt2-1.5b-l12")
    calls = gpt2_step.attention_calls(d, 8, 1024)
    assert calls == [(8, 25, 1024, 64, 64)] * 12
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    one = flash_attention.least_seconds(8, 25, 1024, 64, 64, 4, peaks)
    fwd = flash_attention.least_seconds(8, 25, 1024, 64, 64, 4, peaks,
                                        backward=False)
    # the same float as the layer count times one call's, as before the
    # calls were listed one by one
    assert flash_attention.step_least_seconds(calls, 4, peaks,
                                              "both") == 12 * one
    assert flash_attention.step_least_seconds(calls, 4, peaks,
                                              "fwd") == 12 * fwd
    assert flash_attention.step_least_seconds(calls, 4, peaks,
                                              "bwd") == 12 * (one - fwd)
