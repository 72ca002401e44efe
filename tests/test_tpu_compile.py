"""Compile the main-path Pallas kernels for a TPU v5e chip that is described,
not attached.

Nothing runs: each case lowers and compiles at real model widths with the
chip's own compiler, which refuses what interpret mode accepts (block shapes
that are not legal tiles, too much VMEM).  The topology is described inside
a fixture, never at import: only one process at a time may load the TPU
library, and every test worker imports this file.

The ``xfail(strict=True)`` cases are kernels the compiler still refuses;
each reason quotes the refusal, and a case that starts to compile fails
until its mark is removed.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (flash_attention, flash_decode, flash_decode_paged,
                           ssd, wkv6)
from repro.kernels.moe_gmm.ops import gmm

KERNEL_OP = "tpu_custom_call"
BLOCK_RULE = ("last two dimensions of your block shape are divisible by 8 "
              "and 128")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the library logs to /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU library here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_for_chip(fn, *shapes):
    """Compile ``fn`` at ``shapes`` for the described chip; assert the
    Pallas kernel is in the program."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert KERNEL_OP in compiled.as_text()
    return compiled


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


_KERNEL_CALL = re.compile(r"%([\w.-]+)\.\d+ = .* custom-call\(.*"
                          r'custom_call_target="tpu_custom_call"')


def _kernel_names(compiled):
    """The Pallas kernels' HLO instruction names, without their ``.N``: the
    names the device trace's operations carry."""
    return sorted(m.group(1) for m in map(_KERNEL_CALL.search,
                                          compiled.as_text().splitlines())
                  if m)


def _flash_fwd(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False)


# gpt2-117m: 12 heads of 64; SLW buckets below 128 and not multiples of 128
# run the same kernels as the full 1024, each with the head group and blocks
# its shape is given: whole-sequence blocks up to 776 in bfloat16 (648 in
# float32), 512 x 512 padded above (904)
@pytest.mark.parametrize("direction", ["fwd", "grad"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("seq", [1024, 72, 136, 904, 264, 520, 776])
def test_flash_attention_compiles(one_chip, seq, dtype, direction):
    def loss(q, k, v):
        return _flash_fwd(q, k, v).astype(jnp.float32).sum()

    fn = _flash_fwd if direction == "fwd" else jax.grad(loss,
                                                        argnums=(0, 1, 2))
    x = _sds(one_chip, (8, seq, 12, 64), dtype)
    _compile_for_chip(fn, x, x, x)


# serving prefill at batch 1: twelve heads in all, so one head group
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("seq", [136, 1024])
def test_flash_attention_prefill_compiles(one_chip, seq, dtype):
    x = _sds(one_chip, (1, seq, 12, 64), dtype)
    _compile_for_chip(_flash_fwd, x, x, x)


def test_flash_attention_kernels_carry_stable_names(one_chip):
    # the training step's shape: remat full, and the loss is read, so the
    # forward runs twice (the first pass and the backward's recompute)
    attn = jax.checkpoint(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False))
    step = jax.value_and_grad(lambda q, k, v: attn(q, k, v).sum(),
                              argnums=(0, 1, 2))
    x = _sds(one_chip, (8, 1024, 12, 64))
    names = _kernel_names(_compile_for_chip(step, x, x, x))
    assert names == ["flash_attention_delta", "flash_attention_dkv",
                     "flash_attention_dq", "flash_attention_fwd",
                     "flash_attention_fwd"]
    assert all("flash_attention" in n for n in names)


# moonlight-16b-a3b's latent attention: 16 heads, queries and keys of
# 128 + 64, values of 128, one row of 8192 tokens; under remat full the
# forward runs twice
def test_flash_attention_compiles_at_latent_widths(one_chip):
    attn = jax.checkpoint(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        interpret=False))
    step = jax.value_and_grad(lambda q, k, v: attn(q, k, v).sum(),
                              argnums=(0, 1, 2))
    qk = _sds(one_chip, (1, 8192, 16, 192))
    v = _sds(one_chip, (1, 8192, 16, 128))
    names = _kernel_names(_compile_for_chip(step, qk, qk, v))
    assert names == ["flash_attention_delta", "flash_attention_dkv",
                     "flash_attention_dq", "flash_attention_fwd",
                     "flash_attention_fwd"]


# the held experts' products of one moonlight-16b-a3b MoE layer at 1 x 8192
# tokens: 6 choices each, rows sorted over the router's 64 experts, 8 of
# them (1408 wide) held here from expert 8; forward and backward
@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_moe_gmm_compiles_and_carries_stable_names(one_chip, k, n):
    def loss(x, w, sizes):
        return gmm(x, w, sizes, 8, interpret=False).sum()

    step = jax.value_and_grad(loss, argnums=(0, 1))
    names = _kernel_names(_compile_for_chip(
        step, _sds(one_chip, (8192 * 6, k)), _sds(one_chip, (8, k, n)),
        _sds(one_chip, (64,), jnp.int32)))
    assert names == ["moe_gmm", "moe_gmm", "moe_tgmm"]


def test_flash_decode_compiles(one_chip):
    q = _sds(one_chip, (8, 12, 64))
    cache = _sds(one_chip, (8, 2048, 12, 64))
    lengths = _sds(one_chip, (8,), jnp.int32)
    compiled = _compile_for_chip(
        lambda q, k, v, n: flash_decode(q, k, v, n, interpret=False),
        q, cache, cache, lengths)
    assert _kernel_names(compiled) == ["flash_decode"]


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "zamba2-2.7b SSD: the rank-1 block (1,) over a_coef (H,) is refused; "
    "rank-1 blocks must equal the array or be a multiple of 128"))
def test_ssd_compiles_at_zamba2_widths(one_chip):
    b, h, s, p, n = 1, 80, 1024, 64, 64  # d_model 2560 x expand 2 / 64
    _compile_for_chip(
        lambda *a: ssd(*a, interpret=False)[0],
        _sds(one_chip, (b, h, s, p)), _sds(one_chip, (b, h, s)),
        _sds(one_chip, (h,)), _sds(one_chip, (b, s, n)),
        _sds(one_chip, (b, s, n)))


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "rwkv6-7b WKV6: the u block (1, 64) over (64, 64) is refused: "
    + BLOCK_RULE))
def test_wkv6_compiles_at_rwkv6_widths(one_chip):
    x = _sds(one_chip, (1, 64, 1024, 64))  # 64 heads of 64
    _compile_for_chip(lambda *a: wkv6(*a, interpret=False)[0],
                      x, x, x, x, _sds(one_chip, (64, 64)))


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "zamba2-2.7b paged decode: the k/v page block (1, page, 1, 80) over the "
    "(n_pages, page, 32, 80) pool is refused: " + BLOCK_RULE))
def test_flash_decode_paged_compiles_at_zamba2_widths(one_chip):
    slots, pages_per_slot, page = 8, 16, 128
    pool = _sds(one_chip, (slots * pages_per_slot, page, 32, 80))
    _compile_for_chip(
        lambda *a: flash_decode_paged(*a, interpret=False),
        _sds(one_chip, (slots, 32, 80)), pool, pool,
        _sds(one_chip, (slots, pages_per_slot), jnp.int32),
        _sds(one_chip, (slots,), jnp.int32))
