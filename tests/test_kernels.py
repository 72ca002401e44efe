"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import flash_attention, ssd, wkv6
from repro.kernels.flash_attention.ref import (attention_reference,
                                               attention_reference_gqa)
from repro.kernels.rwkv6.ref import wkv6_fwd_reference, wkv6_sequential
from repro.kernels.ssd.ref import ssd_fwd_reference

TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOLS[dtype]


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,kv,d", [
    (2, 128, 4, 2, 64),
    (1, 256, 8, 8, 32),   # MHA
    (2, 192, 6, 2, 16),   # uneven blocks (padding path)
    (1, 64, 4, 1, 128),   # MQA
    (2, 136, 4, 2, 64),   # an SLW bucket that pads, GQA, 8 heads a step
    (2, 8, 6, 3, 16),     # the first SLW bucket: one block, 12 heads a step
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, s, h, kv, d, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(s + h), 3)
    q = jax.random.normal(ks[0], (b, s, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, d)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    g = h // kv
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = jnp.repeat(k, g, 2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = jnp.repeat(v, g, 2).transpose(0, 2, 1, 3).reshape(b * h, s, d)
    ref = attention_reference(qf.astype(jnp.float32), kf.astype(jnp.float32),
                              vf.astype(jnp.float32), causal=causal)
    ref = ref.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("b,s,h,kv,d,causal", [
    (2, 128, 4, 2, 32, True),    # causal + GQA
    (1, 160, 4, 1, 16, True),    # padded tail (160 % 64 != 0) + MQA
    (2, 96, 6, 2, 16, False),    # non-causal + padding + GQA
    (1, 128, 4, 4, 32, True),    # MHA
    (2, 136, 4, 2, 64, True),    # SLW bucket 136: pads, GQA, 8 heads a step
    (2, 8, 6, 3, 16, True),      # SLW bucket 8: one block, 12 heads a step
])
def test_flash_attention_grads_match_reference(b, s, h, kv, d, causal):
    """dq/dk/dv of the custom_vjp path vs jax.grad of the dense oracle."""
    ks = jax.random.split(jax.random.PRNGKey(7 * s + h), 4)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    w = jax.random.normal(ks[3], (b, s, h, d))  # non-trivial cotangent

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                              interpret=True)
        return jnp.sum(out * w)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference_gqa(q, k, v, causal=causal) * w)

    grads = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    grads_ref = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for name, g, gr in zip(("dq", "dk", "dv"), grads, grads_ref):
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=1e-4,
                                   rtol=1e-4, err_msg=name)


def test_flash_attention_grads_mixed_blocks():
    """block_q != block_k exercises the clamped causal index maps on both
    bwd kernels."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b, s, h, kv, d = 1, 128, 2, 1, 16
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))

    def loss(fn):
        return lambda q, k, v: jnp.sum(jnp.tanh(fn(q, k, v)))

    fa = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=32,
                                         block_k=64, interpret=True)
    ref = lambda q, k, v: attention_reference_gqa(q, k, v, causal=True)
    g = jax.grad(loss(fa), (0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4,
                                   rtol=1e-4)


@pytest.mark.parametrize("s", [8, 136])
def test_flash_attention_chosen_tiles_match_blockwise(s):
    """With no blocks given the chooser folds every head into one grid
    step (block_h = B*H here); forward and gradients match the model's
    blockwise attention, causal with GQA (G = 2)."""
    from repro.kernels.flash_attention.kernel import tiles
    from repro.models.attention import blockwise_attention
    b, h, kv, d = 2, 4, 2, 64
    assert tiles(b * h, s, d, 4)[0] == b * h
    ks = jax.random.split(jax.random.PRNGKey(s), 4)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    w = jax.random.normal(ks[3], (b, s, h, d))
    fa = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                         interpret=True)
    ref = lambda q, k, v: blockwise_attention(q, k, v, causal=True,
                                              block_kv=32)
    np.testing.assert_allclose(np.asarray(fa(q, k, v)),
                               np.asarray(ref(q, k, v)), atol=2e-5,
                               rtol=2e-5)
    loss = lambda fn: (lambda q, k, v: jnp.sum(fn(q, k, v) * w))
    g = jax.grad(loss(fa), (0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    for name, a, b_ in zip(("dq", "dk", "dv"), g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4,
                                   rtol=1e-4, err_msg=name)


# the SLW cell (32 rows x 12 heads of 64, float32): bucket -> (block_h,
# block, padded length, grid steps per call of the forward, dQ and dK/dV;
# the delta kernel takes one step per q block).  One head and 128 x 128
# tiles took 384 * (S_pad / 128)^2: 384 at seq 8, 1,536 at 136, 24,576 at
# 1024.
SLW_TILES = {
    8: (64, 8, 8, 6),
    136: (12, 136, 136, 32),
    264: (6, 264, 264, 64),
    392: (4, 392, 392, 96),
    520: (2, 520, 520, 192),
    648: (2, 648, 648, 192),
    776: (3, 512, 1024, 512),
    904: (3, 512, 1024, 512),
    1024: (3, 512, 1024, 512),
}


def _grid(bh, s, tile):
    import math
    block_h, bq, bk = tile
    s_pad = s + (-s) % math.lcm(bq, bk)
    return s_pad, (bh // block_h) * (s_pad // bq) * (s_pad // bk)


@pytest.mark.parametrize("s", sorted(SLW_TILES))
def test_flash_tiles_for_the_slw_buckets(s):
    from repro.kernels.flash_attention.kernel import (VMEM_BUDGET, tiles,
                                                      vmem_bytes)
    block_h, block, s_pad, steps = SLW_TILES[s]
    tile = tiles(384, s, 64, 4)
    assert tile == (block_h, block, block)
    assert _grid(384, s, tile) == (s_pad, steps)
    assert vmem_bytes(*tile, 64, 4) <= VMEM_BUDGET


@pytest.mark.parametrize("bh,s,itemsize,want", [
    (12, 8, 4, (12, 8, 8)),          # serving prefill at batch 1
    (12, 136, 4, (12, 136, 136)),
    (12, 1024, 4, (3, 512, 512)),
    (21, 136, 4, (7, 136, 136)),     # 3 x 7 heads: no divisor 8 or 16
    (21, 520, 4, (1, 520, 520)),     # fits two heads, but 21 is odd
    (21, 1024, 4, (3, 512, 512)),
    (384, 136, 2, (16, 136, 136)),   # bfloat16 blocks are half the bytes
    (384, 776, 2, (2, 776, 776)),
    (384, 1024, 2, (4, 512, 512)),
    (1, 1024, 4, (1, 1024, 1024)),   # one head: the whole sequence
])
def test_flash_tiles_adapt_to_the_shapes(bh, s, itemsize, want):
    from repro.kernels.flash_attention.kernel import (VMEM_BUDGET, tiles,
                                                      vmem_bytes)
    assert tiles(bh, s, 64, itemsize) == want
    if want[0] > 1:
        assert vmem_bytes(*want, 64, itemsize) <= VMEM_BUDGET


# latent attention: queries and keys d_qk wide, values d_v (Moonlight's
# 192 / 128 among them, at a short sequence)
@pytest.mark.parametrize("b,s,h,kv,d,d_v,causal", [
    (1, 64, 4, 2, 48, 32, True),
    (2, 136, 4, 4, 24, 16, True),     # padded tail, several blocks
    (1, 96, 2, 1, 32, 64, False),     # values wider than keys
    (1, 128, 2, 2, 192, 128, True),   # Moonlight's head widths
])
def test_flash_attention_narrow_values_match_reference(b, s, h, kv, d, d_v,
                                                       causal):
    ks = jax.random.split(jax.random.PRNGKey(11 * s + d), 4)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d_v))
    w = jax.random.normal(ks[3], (b, s, h, d_v))

    def fa(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=64,
                               block_k=64, interpret=True)

    def ref(q, k, v):
        return attention_reference_gqa(q, k, v, causal=causal)

    out = fa(q, k, v)
    assert out.shape == (b, s, h, d_v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda *a: jnp.sum(fa(*a) * w), (0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) * w), (0, 1, 2))(q, k, v)
    for name, a, b_ in zip(("dq", "dk", "dv"), g, gr):
        assert a.shape == b_.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4,
                                   rtol=1e-4, err_msg=name)


def test_flash_tiles_with_narrow_values():
    """Where d_v = d the VMEM count and the tiles are the ones computed
    without a d_v (GPT-2's cells keep their tiles); Moonlight's 192 / 128
    heads at 8192 take 512 x 512 tiles of two heads, within the budget."""
    from repro.kernels.flash_attention.kernel import (VMEM_BUDGET, tiles,
                                                      vmem_bytes)
    for bq, bk in ((8, 8), (136, 136), (512, 512), (1024, 1024), (64, 128)):
        for d in (16, 64, 192):
            for item in (2, 4):
                assert vmem_bytes(3, bq, bk, d, item, d_v=d) == \
                    vmem_bytes(3, bq, bk, d, item)
    for bh, s in ((384, 1024), (200, 1024), (96, 136), (12, 1024)):
        assert tiles(bh, s, 64, 4, d_v=64) == tiles(bh, s, 64, 4)
    assert tiles(200, 1024, 64, 4) == (2, 512, 512)   # gpt2-1.5b-l12
    assert tiles(16, 8192, 192, 4, d_v=128) == (2, 512, 512)
    assert vmem_bytes(2, 512, 512, 192, 4, d_v=128) <= VMEM_BUDGET
    # the narrower values take less than keys as wide as the queries
    assert vmem_bytes(2, 512, 512, 192, 4, d_v=128) < \
        vmem_bytes(2, 512, 512, 192, 4)


def test_flash_tiles_keep_given_blocks():
    """Blocks passed in win (clamped to the sequence); a block given alone
    pairs with 128; the head group is still chosen for them."""
    from repro.kernels.flash_attention.kernel import (VMEM_BUDGET, tiles,
                                                      vmem_bytes)
    assert tiles(384, 1024, 64, 4, 64, 64) == (32, 64, 64)
    assert tiles(384, 1024, 64, 4, 256) == (12, 256, 128)
    assert tiles(8, 96, 16, 4, 64, 128) == (8, 64, 96)
    assert vmem_bytes(32, 64, 64, 64, 4) <= VMEM_BUDGET
    assert vmem_bytes(48, 64, 64, 64, 4) > VMEM_BUDGET


def test_flash_attention_head_groups_match_one_head_per_step():
    """Folding heads into a grid step changes the tiling only: per head
    each tile does the same arithmetic, so block_h = 4 matches block_h = 1
    (the grid of one head per step) to float32 rounding, forward and
    backward, with a padded tail."""
    from repro.kernels.flash_attention import kernel as K
    bh, s, d = 8, 192, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q, k, v, do = (jax.random.normal(k_, (bh, s, d)) for k_ in ks)
    kw = dict(block_q=64, block_k=64, valid_len=160, interpret=True)
    runs = []
    for h in (1, 4):
        o, lse = K.flash_attention_fwd(q, k, v, block_h=h, **kw)
        runs.append((o, lse) + K.flash_attention_bwd(q, k, v, o, lse, do,
                                                     block_h=h, **kw))
    for name, a, b_ in zip(("o", "lse", "dq", "dk", "dv"), *runs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6,
                                   rtol=1e-6, err_msg=name)


def test_flash_attention_lcm_padding():
    """s=96 with block_q=64, block_k=128 clamps to bk=96, which is not a
    multiple of bq — the padded length must round up to lcm(bq, bk)
    (this shape used to trip the kernel's divisibility assert)."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 96, 2, 16))
    k = jax.random.normal(ks[1], (1, 96, 1, 16))
    v = jax.random.normal(ks[2], (1, 96, 1, 16))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=128,
                          interpret=True)
    ref = attention_reference_gqa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)


def test_train_step_flash_backend_matches_blockwise():
    """A real train step (jax.value_and_grad through the transformer) with
    attn_backend="flash_interpret" runs the Pallas fwd+bwd kernels and
    matches the blockwise backend's loss per step."""
    from repro.configs import get_arch, reduced
    from repro.configs.base import OptimizerConfig
    from repro.launch import steps as steps_lib
    from repro.models import model_zoo

    base = reduced(get_arch("gpt2-117m").model).replace(
        vocab_size=256, n_layers=1, max_seq_len=64)
    batch = model_zoo.make_train_batch(jax.random.PRNGKey(0), base, 2, 64)
    losses = {}
    for backend in ("blockwise", "flash_interpret"):
        cfg = base.replace(attn_backend=backend)
        model = model_zoo.build_model(cfg, dtype=jnp.float32, remat="none")
        state = steps_lib.init_train_state(jax.random.PRNGKey(1), cfg)
        step = jax.jit(steps_lib.make_train_step(model, OptimizerConfig()))
        per_step = []
        for _ in range(2):
            state, out = step(state, batch, jnp.float32(1e-3))
            per_step.append(float(out["loss"]))
        losses[backend] = per_step
        assert all(np.isfinite(l) for l in per_step), (backend, per_step)
    np.testing.assert_allclose(losses["flash_interpret"],
                               losses["blockwise"], atol=1e-3, rtol=1e-3)


def test_train_loop_flash_backend_no_nans():
    """A reduced GPT-2 `train()` run with the flash backend (interpret mode
    on this CPU container) completes without NaNs and its per-step losses
    match the blockwise backend to <=1e-3."""
    from repro.configs import get_arch, reduced
    from repro.configs.base import OptimizerConfig, SLWConfig, TrainConfig
    from repro.launch.train import train

    def tc(backend):
        cfg = reduced(get_arch("gpt2-117m").model).replace(
            vocab_size=256, n_layers=1, max_seq_len=64, attn_backend=backend)
        return TrainConfig(
            model=cfg,
            optimizer=OptimizerConfig(lr=1e-3, schedule="constant",
                                      total_steps=4, total_tokens=4 * 2 * 32),
            slw=SLWConfig(enabled=False),
            seq_len=32, global_batch=2, remat="none", eval_interval=0)

    res_flash = train(tc("flash_interpret"), quiet=True)
    res_block = train(tc("blockwise"), quiet=True)
    assert res_flash.steps == 4 and not res_flash.diverged
    assert all(np.isfinite(l) for l in res_flash.loss_history)
    np.testing.assert_allclose(res_flash.loss_history, res_block.loss_history,
                               atol=1e-3, rtol=1e-3)


def test_flash_backend_falls_back_off_tpu():
    """attn_backend="flash" must lower/compute on CPU (blockwise fallback),
    so full-scale presets stay dry-runnable on any backend."""
    from repro.configs import get_arch, reduced
    from repro.models import model_zoo

    cfg = reduced(get_arch("gpt2-117m").model).replace(
        vocab_size=256, n_layers=1, attn_backend="flash")
    model = model_zoo.build_model(cfg, dtype=jnp.float32, remat="none")
    params = model_zoo.init_params(jax.random.PRNGKey(0), cfg)
    batch = model_zoo.make_train_batch(jax.random.PRNGKey(2), cfg, 2, 32)
    loss, _ = jax.jit(model.loss)(params, batch)
    assert np.isfinite(float(loss))


# ---------------------------------------------------------------------------
# shared backend/interpret resolution (kernels/__init__.py)
# ---------------------------------------------------------------------------

def test_resolve_interpret_defaults():
    """One shared rule for all three kernels: explicit flags pass through,
    None means compiled on TPU / interpret everywhere else."""
    from repro.kernels import on_tpu, resolve_interpret
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    assert resolve_interpret(None) == (not on_tpu())
    assert on_tpu() == (jax.default_backend() == "tpu")
    if jax.default_backend() != "tpu":  # this container: CPU
        assert resolve_interpret(None) is True


def test_resolve_backend_and_chunk_padding():
    from repro.kernels import chunk_padding, on_tpu, resolve_backend
    assert resolve_backend("reference", "ssm_backend") == (False, False)
    assert resolve_backend("kernel_interpret", "ssm_backend") == (True, True)
    use_kernel, interp = resolve_backend("kernel", "ssm_backend")
    assert use_kernel == on_tpu() and interp is False
    with pytest.raises(ValueError, match="rwkv_backend"):
        resolve_backend("flash", "rwkv_backend")
    assert chunk_padding(128, 32) == (32, 0)
    assert chunk_padding(100, 32) == (32, 28)   # uneven tail
    assert chunk_padding(48, 64) == (48, 0)     # chunk clamped to s


def test_unknown_mix_backends_raise():
    from repro.configs import get_arch, reduced
    from repro.models.mamba2 import ssd_mix
    from repro.models.rwkv6 import wkv6_mix
    z = jnp.zeros((1, 16, 2, 4))
    cfg = reduced(get_arch("zamba2-2.7b").model).replace(ssm_backend="nope")
    with pytest.raises(ValueError, match="ssm_backend"):
        ssd_mix(z, jnp.zeros((1, 16, 2)), jnp.zeros((2,)),
                jnp.zeros((1, 16, 4)), jnp.zeros((1, 16, 4)), cfg)
    cfg = reduced(get_arch("rwkv6-7b").model).replace(rwkv_backend="nope")
    with pytest.raises(ValueError, match="rwkv_backend"):
        wkv6_mix(z, z, z, z, jnp.zeros((2, 4)), cfg)


# ---------------------------------------------------------------------------
# SSD (Mamba-2)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,p,n,chunk", [
    (2, 3, 128, 16, 8, 32),
    (1, 2, 256, 32, 16, 64),
    (1, 1, 64, 64, 64, 64),  # single chunk
    (1, 2, 100, 16, 8, 32),  # uneven tail (padding path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_sweep(b, h, s, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(s + p), 5)
    x = jax.random.normal(ks[0], (b, h, s, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h, s))).astype(jnp.float32)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bi = jax.random.normal(ks[3], (b, s, n)).astype(dtype)
    ci = jax.random.normal(ks[4], (b, s, n)).astype(dtype)
    y, st = ssd(x, dt, a, bi, ci, chunk=chunk, interpret=True)
    yr, sr = ssd_fwd_reference(x.astype(jnp.float32), dt, a,
                               bi.astype(jnp.float32),
                               ci.astype(jnp.float32), chunk=chunk)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=5 * _tol(dtype), rtol=5 * _tol(dtype))
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr),
                               atol=5 * _tol(dtype), rtol=5 * _tol(dtype))


# ---------------------------------------------------------------------------
# RWKV6 / WKV
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,h,s,d,chunk", [
    (2, 3, 96, 16, 32),
    (1, 2, 128, 32, 16),
    (1, 1, 32, 64, 32),
    (1, 2, 50, 16, 16),  # uneven tail (padding path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_sweep(b, h, s, d, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(s + d), 5)
    r = jax.random.normal(ks[0], (b, h, s, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, h, s, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, h, s, d)).astype(dtype)
    lw = -jnp.exp(jax.random.normal(ks[3], (b, h, s, d)) * 0.5)
    lw = lw.astype(jnp.float32)
    u = (jax.random.normal(ks[4], (h, d)) * 0.5).astype(jnp.float32)
    y, st = wkv6(r, k, v, lw, u, chunk=chunk, interpret=True)
    yr, sr = wkv6_sequential(r.astype(jnp.float32), k.astype(jnp.float32),
                             v.astype(jnp.float32), lw, u)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=10 * _tol(dtype), rtol=10 * _tol(dtype))
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr),
                               atol=10 * _tol(dtype), rtol=10 * _tol(dtype))


def test_wkv6_chunked_matches_chunked_ref():
    """Kernel vs the model's own chunked formulation (not just sequential)."""
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    b, h, s, d = 1, 2, 64, 16
    r, k, v = (jax.random.normal(ks[i], (b, h, s, d)) for i in range(3))
    lw = -jnp.exp(jax.random.normal(ks[3], (b, h, s, d)) * 0.5)
    u = jax.random.normal(ks[4], (h, d)) * 0.5
    y, st = wkv6(r, k, v, lw, u, chunk=16, interpret=True)
    yr, sr = wkv6_fwd_reference(r, k, v, lw, u, chunk=32)  # different chunking
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4,
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=1e-4,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# SSD / WKV6 gradients (custom_vjp through the Pallas reverse-scan kernels)
# ---------------------------------------------------------------------------

# per-dtype grad tolerances: f32 per the acceptance bar; bf16 inputs round
# the f32-accumulated cotangents back to 8-bit mantissas on output
GRAD_TOLS = {jnp.float32: 1e-4, jnp.bfloat16: 4e-2}

# (s, chunk): single-chunk, many-chunk, uneven tail, chunk clamped to s
SEQ_CHUNK_CASES = [(64, 64), (128, 32), (100, 32), (48, 64)]


@pytest.mark.parametrize("s,chunk", SEQ_CHUNK_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_grads_match_reference(s, chunk, dtype):
    """jax.grad of a scalar loss (with y *and* final-state cotangents)
    through the ssd custom_vjp vs. grad through the jnp chunked oracle."""
    b, h, p, n = 2, 2, 8, 4
    tol = GRAD_TOLS[dtype]
    ks = jax.random.split(jax.random.PRNGKey(3 * s + chunk), 7)
    x = jax.random.normal(ks[0], (b, h, s, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, h, s)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    bi = jax.random.normal(ks[3], (b, s, n)).astype(dtype)
    ci = jax.random.normal(ks[4], (b, s, n)).astype(dtype)
    w = jax.random.normal(ks[5], (b, h, s, p))
    ws = jax.random.normal(ks[6], (b, h, n, p))

    def loss(fn):
        def _l(x, dt, a, bi, ci):
            y, st = fn(x, dt, a, bi, ci)
            return jnp.sum(y.astype(jnp.float32) * w) + jnp.sum(st * ws)
        return _l

    kern = lambda *t: ssd(*t, chunk=chunk, interpret=True)
    ref = lambda *t: ssd_fwd_reference(*t, chunk=chunk)
    gk = jax.grad(loss(kern), (0, 1, 2, 3, 4))(x, dt, a, bi, ci)
    gr = jax.grad(loss(ref), (0, 1, 2, 3, 4))(x, dt, a, bi, ci)
    for name, g, r in zip(("dx", "ddt", "da", "db", "dc"), gk, gr):
        assert g.dtype == r.dtype, name
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r, np.float32),
                                   atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("s,chunk", SEQ_CHUNK_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_grads_match_reference(s, chunk, dtype):
    """jax.grad through the wkv6 custom_vjp (dr/dk/dv/d_log_w/du) vs. grad
    through the jnp chunked oracle, same loss shape as the ssd test."""
    b, h, d = 2, 2, 8
    tol = GRAD_TOLS[dtype]
    ks = jax.random.split(jax.random.PRNGKey(5 * s + chunk), 7)
    r = jax.random.normal(ks[0], (b, h, s, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, h, s, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, h, s, d)).astype(dtype)
    lw = -jnp.exp(jax.random.normal(ks[3], (b, h, s, d)) * 0.5)
    u = jax.random.normal(ks[4], (h, d)) * 0.5
    w = jax.random.normal(ks[5], (b, h, s, d))
    ws = jax.random.normal(ks[6], (b, h, d, d))

    def loss(fn):
        def _l(r, k, v, lw, u):
            y, st = fn(r, k, v, lw, u)
            return jnp.sum(y.astype(jnp.float32) * w) + jnp.sum(st * ws)
        return _l

    kern = lambda *t: wkv6(*t, chunk=chunk, interpret=True)
    ref = lambda *t: wkv6_fwd_reference(*t, chunk=chunk)
    gk = jax.grad(loss(kern), (0, 1, 2, 3, 4))(r, k, v, lw, u)
    gr = jax.grad(loss(ref), (0, 1, 2, 3, 4))(r, k, v, lw, u)
    for name, g, r_ in zip(("dr", "dk", "dv", "dlw", "du"), gk, gr):
        assert g.dtype == r_.dtype, name
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(r_, np.float32),
                                   atol=tol, rtol=tol, err_msg=name)


def test_wkv6_grads_match_sequential():
    """Independent oracle: grads through the step-by-step lax.scan
    recurrence (not the chunked formulation the kernel mirrors)."""
    b, h, s, d, chunk = 1, 2, 48, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    r, k, v = (jax.random.normal(ks[i], (b, h, s, d)) for i in range(3))
    lw = -jnp.exp(jax.random.normal(ks[3], (b, h, s, d)) * 0.5)
    u = jax.random.normal(ks[4], (h, d)) * 0.5
    w = jax.random.normal(ks[5], (b, h, s, d))

    def loss(fn):
        return lambda *t: jnp.sum(fn(*t)[0] * w)

    kern = lambda *t: wkv6(*t, chunk=chunk, interpret=True)
    gk = jax.grad(loss(kern), (0, 1, 2, 3, 4))(r, k, v, lw, u)
    gr = jax.grad(loss(wkv6_sequential), (0, 1, 2, 3, 4))(r, k, v, lw, u)
    for name, g, r_ in zip(("dr", "dk", "dv", "dlw", "du"), gk, gr):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r_), atol=1e-4,
                                   rtol=1e-4, err_msg=name)


# ---------------------------------------------------------------------------
# backend parity: ssm_backend / rwkv_backend through real model train steps
# ---------------------------------------------------------------------------

def _train_step_outputs(cfg, batch, steps=2):
    from repro.configs.base import OptimizerConfig
    from repro.launch import steps as steps_lib
    from repro.models import model_zoo
    model = model_zoo.build_model(cfg, dtype=jnp.float32, remat="none")
    state = steps_lib.init_train_state(jax.random.PRNGKey(1), cfg)
    step = jax.jit(steps_lib.make_train_step(model, OptimizerConfig()))
    out_hist = []
    for _ in range(steps):
        state, out = step(state, batch, jnp.float32(1e-3))
        out_hist.append((float(out["loss"]), float(out["grad_norm"])))
    return out_hist


def _backend_parity_case(arch, field, seq_len=48):
    """seq_len=48 is deliberately not a multiple of the reduced chunk
    sizes (32/16), so the kernel's uneven-tail padding runs in-model."""
    from repro.configs import get_arch, reduced
    from repro.models import model_zoo
    base = reduced(get_arch(arch).model).replace(
        vocab_size=256, max_seq_len=64, n_layers=2,
        **({"attn_every": 2} if arch == "zamba2-2.7b" else {}))
    batch = model_zoo.make_train_batch(jax.random.PRNGKey(0), base, 2,
                                       seq_len)
    outs = {}
    for backend in ("reference", "kernel_interpret"):
        cfg = base.replace(**{field: backend})
        outs[backend] = _train_step_outputs(cfg, batch)
        assert all(np.isfinite(x) for pair in outs[backend] for x in pair)
    np.testing.assert_allclose(outs["kernel_interpret"], outs["reference"],
                               atol=1e-4, rtol=1e-4)


def test_train_step_rwkv_kernel_backend_matches_reference():
    """RWKV6 train steps (loss + grad-norm) through the Pallas WKV fwd+bwd
    kernels match the reference backend."""
    _backend_parity_case("rwkv6-7b", "rwkv_backend")


def test_train_step_ssm_kernel_backend_matches_reference():
    """Zamba2 (Mamba-2 backbone) train steps through the Pallas SSD fwd+bwd
    kernels match the reference backend."""
    _backend_parity_case("zamba2-2.7b", "ssm_backend")


def test_mamba2_block_kernel_backend_grads_match_reference():
    """Block-level Mamba-2 parity: value and parameter gradients of a full
    mamba2_block agree between the reference scan and the kernel backend."""
    from repro.configs import get_arch, reduced
    from repro.models import layers as L
    from repro.models.mamba2 import mamba2_block, mamba2_def
    cfg = reduced(get_arch("zamba2-2.7b").model)
    lp = L.init_params(jax.random.PRNGKey(0), mamba2_def(cfg))
    x = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.d_model))

    def make_loss(c):
        w = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
        return lambda lp: jnp.sum(mamba2_block(lp, x, c) * w)

    vals, grads = {}, {}
    for backend in ("reference", "kernel_interpret"):
        c = cfg.replace(ssm_backend=backend)
        vals[backend], grads[backend] = jax.value_and_grad(make_loss(c))(lp)
    np.testing.assert_allclose(float(vals["kernel_interpret"]),
                               float(vals["reference"]), atol=1e-4, rtol=1e-4)
    flat_k = jax.tree_util.tree_leaves(grads["kernel_interpret"])
    flat_r = jax.tree_util.tree_leaves(grads["reference"])
    for g, r in zip(flat_k, flat_r):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-4,
                                   rtol=1e-3)


def test_kernel_backends_fall_back_off_tpu():
    """ssm_backend/rwkv_backend="kernel" (the full-scale preset setting)
    must lower and compute on CPU via the reference fallback."""
    from repro.configs import get_arch, reduced
    from repro.models import model_zoo
    for arch in ("rwkv6-7b", "zamba2-2.7b"):
        cfg = reduced(get_arch(arch).model).replace(vocab_size=256,
                                                    n_layers=2, **(
            {"attn_every": 2} if arch == "zamba2-2.7b" else {}))
        assert "kernel" in (cfg.rwkv_backend, cfg.ssm_backend)  # inherited
        model = model_zoo.build_model(cfg, dtype=jnp.float32, remat="none")
        params = model_zoo.init_params(jax.random.PRNGKey(0), cfg)
        batch = model_zoo.make_train_batch(jax.random.PRNGKey(2), cfg, 2, 32)
        loss, _ = jax.jit(model.loss)(params, batch)
        assert np.isfinite(float(loss)), arch


def test_model_attention_blockwise_matches_flash_ref():
    """The model's blockwise-scan attention is itself validated against the
    kernel oracle (they must agree — it is the XLA fallback path)."""
    from repro.models.attention import blockwise_attention
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    b, s, h, kv, d = 2, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    out = blockwise_attention(q, k, v, causal=True, block_kv=32)
    ref = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5,
                               rtol=1e-5)
