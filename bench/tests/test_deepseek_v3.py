"""The DeepSeek-V3 architecture's files: its step work and its experts'
work by hand at Moonlight-16B-A3B's cut, the experts' roofline reader,
and a small configuration of it run through the harness to ``correct`` on
the CPU, found by its ``model_type`` alone."""
import json
import time
from types import SimpleNamespace

import jax
import pytest

from bench.harness import weights
from bench.harness.cell import load_cell, metric_reader
from bench.harness.runner import run_cell
from bench.reference import deepseek_v3 as ref
from bench.reference.deepseek_v3 import dims_from_config
from bench.tests.fixture import REPO, make_root
from bench.work import deepseek_v3_step as work
from bench.work import moe_experts

MOONLIGHT = REPO / "bench" / "configs" / "moonlight-16b-a3b-l5.json"


def test_moonlight_step_work_by_hand():
    d = dims_from_config(json.loads(MOONLIGHT.read_text()))
    # one layer's latent attention: q_proj 2048 x 16 x 192, kv_a
    # 2048 x 576, kv_b 512 x 16 x 256, o_proj 16 x 128 x 2048
    assert work.attention_params(d) == 13_762_560
    # per token: 5 attention layers; the dense SwiGLU (3 x 2048 x 11264);
    # 4 MoE layers of router (2048 x 64), shared experts (3 x 2048 x 2816)
    # and 8 of 64 experts at 6 choices (0.75 of 3 x 2048 x 1408); the head
    # over the slice (2048 x 20480)
    per_token = (5 * 13_762_560 + 3 * 2048 * 11264
                 + 4 * (2048 * 64 + 3 * 2048 * 2816 + 0.75 * 3 * 2048 * 1408)
                 + 2048 * 20480)
    assert work.matmul_params(d) == per_token
    # attention: 5 layers x 16 heads x 8192^2 / 2 x 2 x (192 + 128)
    attn = 5 * 16 * (8192 ** 2 / 2) * 2 * (192 + 128)
    fwd = 2 * per_token * 8192 + attn
    assert work.forward_flops(d, 1, 8192) == fwd
    assert round(fwd / 1e12, 3) == 6.234
    assert round(work.train_step_flops(d, 1, 8192) / 1e12, 2) == 18.70
    assert work.attention_calls(d, 1, 8192) == [(1, 16, 8192, 192, 128)] * 5
    assert work.expected_rows(d, 1, 8192) == 768


V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_moonlight_expert_work_by_hand():
    d = dims_from_config(json.loads(MOONLIGHT.read_text()))
    # each MoE layer: 8 held experts at 768 expected rows each
    calls = work.expert_calls(d, 1, 8192)
    assert calls == [(6144, 2048, 1408, 8)] * 4
    # gate and up 6144 x 2048 -> 1408, down 6144 x 1408 -> 2048
    fwd = 3 * 2 * 6144 * 2048 * 1408
    assert moe_experts.flops(*calls[0]) == {"fwd": fwd, "bwd": 2 * fwd}
    # rows in, the 8 experts' weights, rows out, float32, per product
    per_product = 6144 * 2048 + 8 * 2048 * 1408 + 6144 * 1408
    moved = moe_experts.bytes_moved(*calls[0], 4)
    assert moved == {"fwd": 3 * 4 * per_product,
                     "bwd": 2 * 3 * 4 * per_product}
    # ~200 operations a byte, under the v5e's ridge of ~240: bandwidth
    assert 195 < fwd / moved["fwd"] < 205
    least = 3 * moved["fwd"] / V5E["hbm_bytes_per_s"]
    assert moe_experts.least_seconds(*calls[0], 4, V5E) == pytest.approx(
        least)
    assert moe_experts.step_least_seconds(calls, 4, V5E) == pytest.approx(
        4 * least)


class FakeTrace:
    def __init__(self, seconds):
        self.seconds = seconds

    def kernel_s(self, match):
        assert tuple(match) == ("moe_gmm", "moe_tgmm")
        return self.seconds


def _reader_ctx(cell_name, trace, steps):
    cell = load_cell(REPO, cell_name)
    return SimpleNamespace(kind="train", cell=cell, dims=cell.dims,
                           peaks=V5E, trace=trace, steps=steps)


def test_experts_roofline_reads_least_time_over_kernel_time():
    read = metric_reader(REPO, "moe_experts_roofline")
    cell = "moonlight-16b-a3b-l5.train-seq8192"
    least = moe_experts.step_least_seconds(
        [(6144, 2048, 1408, 8)] * 4, 4, V5E)
    got = read(_reader_ctx(cell, FakeTrace(10 * least), [(1, 8192)] * 5))
    assert got == pytest.approx(50.0)
    # no trace, or no expert kernel in it: nothing to read
    assert read(_reader_ctx(cell, None, [(1, 8192)])) is None
    assert read(_reader_ctx(cell, FakeTrace(0.0), [(1, 8192)])) is None
    # an architecture without experts has no expert_calls
    assert read(_reader_ctx("gpt2-1.5b-l12.train-seq1024", FakeTrace(1.0),
                            [(8, 1024)])) is None


def test_the_reference_hands_its_gradient_back_on_the_host():
    d = dims_from_config(SMALL)
    cell = SimpleNamespace(arch=lambda part: ref, dims=d)
    params = weights.make(5, cell)
    tok = jax.numpy.zeros((1, 8), jax.numpy.int32)
    loss, grads = ref.Reference(d).loss_and_grad(params, tok, tok)
    host = jax.devices("cpu")[0]
    for g in jax.tree_util.tree_leaves(grads):
        assert g.committed and g.devices() == {host}
    assert loss > 0


SMALL = {
    "source": "https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json",
    "model_type": "deepseek_v3", "attention_bias": False,
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 64,
    "intermediate_size": 96, "kv_lora_rank": 32,
    "max_position_embeddings": 64, "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 4,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 6, "num_hidden_layers": 3, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 16,
    "vocab_size": 512, "expert_offset": 4,
    "reduced": ["num_hidden_layers", "n_routed_experts", "vocab_size"],
    "published": {"n_routed_experts": 16}, "assumed": [],
    "program": {"dtype": "float32", "matmul_precision": "default",
                "attn_backend": "flash", "remat": "full",
                "moe_dispatch": "dropless"},
}
TRAFFIC = {"kind": "train", "rows": 2, "seq": 32, "warm_steps": 1,
           "ref_rows_per_block": 1,
           "argv": ["--batch", "2", "--seq", "32", "--steps", "100000",
                    "--warmup", "4", "--lr", "4.2e-4",
                    "--eval-interval", "0", "--ckpt-interval", "0"]}
# float32 on the CPU reads loss 8e-8, gradient 1.1e-6, change 7.2e-6 on
# three seeds; the limits leave 14x to 600x of room
LIMITS = {"loss_gap": {"limit": 5e-5}, "grad_gap": {"limit": 5e-5},
          "update_gap": {"limit": 1e-4}}


def test_a_small_deepseek_v3_cell_runs_to_correct(tmp_path):
    root = make_root(tmp_path)
    (root / "bench" / "configs" / "small-v3.json").write_text(
        json.dumps(SMALL))
    (root / "bench" / "traffic" / "small-fixed.json").write_text(
        json.dumps(TRAFFIC))
    cell = "small-v3.small-fixed"
    (root / "bench" / "limits" / f"{cell}.json").write_text(
        json.dumps(LIMITS))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "small-v3", "source": SMALL["source"],
                             "file": "bench/configs/small-v3.json",
                             "reduced": SMALL["reduced"], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "small-v3",
                               "traffic": "small-fixed", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    assert load_cell(root, cell).dims.n_experts == 16
    res = run_cell(root, cell, 2**31 + 11, 1.0, False, time.perf_counter(),
                   require_chip=False)
    assert res["line"]["correct"] is True, res["line"]["checks"]
    assert res["line"]["failed"] == 0
