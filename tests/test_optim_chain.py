"""The composable optimizer chain: legacy-exactness, state migration,
decay masking, the opt-in arms (SM3 / Shampoo / AGC / per-leaf LR), and
per-parameter telemetry driving per-layer blame end to end.

The legacy-parity tests are the contract that lets the chain replace
``adamw_update`` on the hot path: the default chain must reproduce the
legacy trajectory *numerically exactly* (params, opt state, scalar
telemetry), including across a mid-run checkpoint/restore.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt_lib
from repro.checkpoint import migrate_host_state
from repro.configs import get_arch, reduced
from repro.configs.base import (OptimizerConfig, RegulatorSpec, SLWConfig,
                                TrainConfig)
from repro.optim import (adamw_update, adaptive_grad_clip, apply_updates,
                         abstract_chain_state, build_optimizer, chain,
                         clip_by_global_norm, decay_mask_tree,
                         init_opt_state, migrate_opt_state, scale_by_lr,
                         scale_by_sm3, scale_per_leaf)
from repro.optim import transforms as tx_lib


def _toy_params(seed=0):
    """Mixed-shape tree shaped like the model zoo: scan-stacked layer
    leaves under 'layers', a matrix, a bias, a scalar."""
    rng = np.random.RandomState(seed)
    return {
        "embed": jnp.asarray(rng.randn(16, 8), jnp.float32),
        "layers": {
            "w": jnp.asarray(rng.randn(2, 8, 8), jnp.float32),
            "scale": jnp.asarray(rng.randn(2, 8), jnp.float32),
        },
        "bias": jnp.asarray(rng.randn(8), jnp.float32),
        "gain": jnp.asarray(rng.randn(), jnp.float32),
    }


def _grads_like(params, seed):
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(*p.shape), jnp.float32), params)


def _legacy_step(params, grads, opt, lr, cfg, clip_scale=1.0):
    clipped, gnorm = clip_by_global_norm(grads, cfg.grad_clip * clip_scale)
    new_p, new_opt, tel = adamw_update(params, clipped, opt,
                                       jnp.float32(lr), cfg)
    tel = dict(tel, grad_norm=gnorm)
    return new_p, new_opt, tel


def _chain_step(tx, params, grads, opt, lr, clip_scale=1.0):
    updates, new_opt, tel = tx.update(
        grads, opt, params,
        {"lr": jnp.float32(lr), "clip_scale": jnp.float32(clip_scale)})
    return apply_updates(params, updates), new_opt, tel


# ---------------------------------------------------------------------------
# legacy parity (the acceptance contract)
# ---------------------------------------------------------------------------

def test_default_chain_matches_legacy_over_50_steps(tmp_path):
    """Default chain == legacy clip+AdamW for 50 steps, bitwise on params
    and opt state, with a checkpoint/restore of the chain state at step 25
    (restore must not perturb the trajectory either)."""
    cfg = OptimizerConfig(lr=3e-3, weight_decay=0.01, grad_clip=1.0)
    tx = build_optimizer(cfg)

    p_legacy = p_chain = _toy_params()
    o_legacy = init_opt_state(p_legacy)
    o_chain = tx.init(p_chain)

    for step in range(50):
        lr = 3e-3 * (0.5 + 0.5 * math.cos(step / 50 * math.pi))
        clip_scale = 0.5 if 20 <= step < 30 else 1.0  # runtime retuning
        g = _grads_like(p_legacy, seed=100 + step)
        p_legacy, o_legacy, t_legacy = _legacy_step(
            p_legacy, g, o_legacy, lr, cfg, clip_scale)
        p_chain, o_chain, t_chain = _chain_step(
            tx, p_chain, g, o_chain, lr, clip_scale)

        if step == 25:  # mid-run checkpoint/restore of the chain state
            ckpt_lib.save(str(tmp_path), step, {"opt": o_chain})
            like = {"opt": abstract_chain_state(
                tx, jax.eval_shape(lambda: p_chain))}
            restored, _ = ckpt_lib.restore(str(tmp_path), step, like)
            o_chain = restored["opt"]

    for a, b in zip(jax.tree_util.tree_leaves(p_legacy),
                    jax.tree_util.tree_leaves(p_chain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(o_legacy),
                    jax.tree_util.tree_leaves(o_chain["adam"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # same scalar telemetry, same values
    for k in ("var_max", "var_l1", "grad_norm"):
        assert float(t_legacy[k]) == float(t_chain[k]), k


def test_chain_state_layout_and_abstract_shapes():
    cfg = OptimizerConfig()
    tx = build_optimizer(cfg)
    p = _toy_params()
    st = tx.init(p)
    assert sorted(st.keys()) == ["adam", "clip", "decay", "lr"]
    assert st["clip"] == {} and st["lr"] == {}
    abs_st = abstract_chain_state(tx, jax.eval_shape(lambda: p))
    assert (jax.tree_util.tree_structure(abs_st)
            == jax.tree_util.tree_structure(st))


# ---------------------------------------------------------------------------
# legacy checkpoint / host-state migration (satellite: migrate tests)
# ---------------------------------------------------------------------------

def test_restore_legacy_flat_opt_checkpoint_into_chain(tmp_path):
    """A pre-chain checkpoint stored the AdamW state flat under ``opt/``;
    restoring into the chain layout must remap it into the ``adam`` slot."""
    p = _toy_params()
    legacy_opt = init_opt_state(p)
    # march the legacy state so the payload is non-trivial
    cfg = OptimizerConfig(lr=1e-2)
    p2, legacy_opt, _ = adamw_update(p, _grads_like(p, 7), legacy_opt,
                                     jnp.float32(1e-2), cfg)
    ckpt_lib.save(str(tmp_path), 3, {"params": p2, "opt": legacy_opt})

    tx = build_optimizer(cfg)
    like = {"params": jax.eval_shape(lambda: p2),
            "opt": abstract_chain_state(tx, jax.eval_shape(lambda: p2))}
    restored, _ = ckpt_lib.restore(str(tmp_path), 3, like)
    assert int(restored["opt"]["adam"]["count"]) == 1
    for a, b in zip(jax.tree_util.tree_leaves(legacy_opt["m"]),
                    jax.tree_util.tree_leaves(restored["opt"]["adam"]["m"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_migrate_host_state_upgrades_legacy_opt():
    host = {"opt": {"m": {"w": [1.0]}, "v": {"w": [2.0]}, "count": 5},
            "controller": {"step": 5}}
    out = migrate_host_state(host)
    assert out["opt"]["adam"]["count"] == 5
    assert out["opt"]["clip"] == {} and out["opt"]["lr"] == {}
    # already-migrated passes through untouched
    assert migrate_opt_state(out["opt"]) is out["opt"]


# ---------------------------------------------------------------------------
# decay mask (satellite: the decay-every-leaf fix)
# ---------------------------------------------------------------------------

def test_decay_mask_std_exempts_norm_gains_and_biases():
    p = _toy_params()
    mask = decay_mask_tree(p, "std")
    assert mask["embed"] is True            # matrix: decays
    assert mask["layers"]["w"] is True      # stacked matrices: decay
    assert mask["layers"]["scale"] is False  # stacked norm gain (L, d): no
    assert mask["bias"] is False
    assert mask["gain"] is False
    # legacy mode decays everything (the old behavior, still the default)
    assert all(jax.tree_util.tree_leaves(decay_mask_tree(p, "all")))
    with pytest.raises(ValueError):
        decay_mask_tree(p, "nope")


def test_adamw_std_mask_leaves_gains_undecayed():
    """Regression for the decay-every-leaf bug: with zero grads the Adam
    core contributes nothing, so the only movement is weight decay — masked
    leaves must not move at all under decay_mask='std'."""
    cfg = OptimizerConfig(lr=1e-2, weight_decay=0.1, decay_mask="std")
    p = _toy_params()
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p)
    new_p, _, _ = adamw_update(p, zeros, init_opt_state(p),
                               jnp.float32(cfg.lr), cfg)
    np.testing.assert_array_equal(np.asarray(new_p["bias"]),
                                  np.asarray(p["bias"]))
    np.testing.assert_array_equal(np.asarray(new_p["layers"]["scale"]),
                                  np.asarray(p["layers"]["scale"]))
    # while matrices did decay
    assert not np.array_equal(np.asarray(new_p["embed"]),
                              np.asarray(p["embed"]))
    # and the chain applies the identical mask
    tx = build_optimizer(cfg)
    chain_p, _, _ = _chain_step(tx, p, zeros, tx.init(p), cfg.lr)
    for a, b in zip(jax.tree_util.tree_leaves(new_p),
                    jax.tree_util.tree_leaves(chain_p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the opt-in arms
# ---------------------------------------------------------------------------

def test_sm3_memory_shape_and_descent():
    cfg = OptimizerConfig(optimizer="sm3", lr=1e-2, weight_decay=0.0,
                          grad_clip=0.0, sm3_momentum=0.9)
    tx = build_optimizer(cfg)
    p = _toy_params()
    st = tx.init(p)
    # accumulators are per-dimension, not per-element: a (16, 8) leaf costs
    # 16 + 8 floats, not 128.  Leaves flatten in sorted-key order:
    # bias, embed, gain, layers/scale, layers/w
    accs = st["sm3"]["acc"][1]  # embed (16, 8)
    assert [a.shape for a in accs] == [(16, 1), (1, 8)]
    g = _grads_like(p, 3)
    new_p, new_st, tel = _chain_step(tx, p, g, st, 1e-2)
    assert "var_max" in tel and np.isfinite(float(tel["var_max"]))
    # the update moved every leaf
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(new_p)):
        assert not np.array_equal(np.asarray(a), np.asarray(b))


def test_shampoo_grafts_adam_norm():
    """The Shampoo direction is rescaled per block to the Adam update norm:
    block norms of the final update must match the Adam arm's block norms."""
    cfg_sh = OptimizerConfig(optimizer="shampoo", lr=1e-2, weight_decay=0.0,
                             grad_clip=0.0, shampoo_interval=1)
    cfg_ad = dataclasses.replace(cfg_sh, optimizer="adamw")
    p = {"w": jnp.asarray(np.random.RandomState(0).randn(2, 8, 8),
                          jnp.float32)}
    g = _grads_like(p, 5)
    hyper = {"lr": jnp.float32(1.0), "clip_scale": jnp.float32(1.0)}

    tx_sh = build_optimizer(cfg_sh)
    u_sh, _, _ = tx_sh.update(g, tx_sh.init(p), p, hyper)
    tx_ad = build_optimizer(cfg_ad)
    u_ad, _, _ = tx_ad.update(g, tx_ad.init(p), p, hyper)

    n_sh = np.sqrt(np.sum(np.asarray(u_sh["w"]) ** 2, axis=(-2, -1)))
    n_ad = np.sqrt(np.sum(np.asarray(u_ad["w"]) ** 2, axis=(-2, -1)))
    np.testing.assert_allclose(n_sh, n_ad, rtol=1e-5)
    # but the direction differs (the preconditioner did something)
    assert not np.allclose(np.asarray(u_sh["w"]), np.asarray(u_ad["w"]),
                           rtol=1e-3)


def test_shampoo_ineligible_leaf_falls_back_to_adam():
    cfg = OptimizerConfig(optimizer="shampoo", lr=1e-2, weight_decay=0.0,
                          grad_clip=0.0, shampoo_block_size=4)
    p = {"big": jnp.ones((8, 8)), "vec": jnp.ones((5,))}  # both ineligible
    tx = build_optimizer(cfg)
    st = tx.init(p)
    assert st["shampoo"]["stats"] == (None, None)
    cfg_ad = dataclasses.replace(cfg, optimizer="adamw")
    tx_ad = build_optimizer(cfg_ad)
    g = _grads_like(p, 9)
    hyper = {"lr": jnp.float32(1.0), "clip_scale": jnp.float32(1.0)}
    u_sh, _, _ = tx.update(g, st, p, hyper)
    u_ad, _, _ = tx_ad.update(g, tx_ad.init(p), p, hyper)
    for a, b in zip(jax.tree_util.tree_leaves(u_sh),
                    jax.tree_util.tree_leaves(u_ad)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_agc_clips_by_grad_to_weight_ratio():
    agc = adaptive_grad_clip(clipping=0.1)
    p = {"w": jnp.full((4,), 2.0)}          # ||p|| = 4
    g_small = {"w": jnp.full((4,), 0.05)}   # ||g|| = 0.1 < 0.1*4: untouched
    g_big = {"w": jnp.full((4,), 5.0)}      # ||g|| = 10  > 0.4: clipped
    out, _, _ = agc.update(g_small, {}, p, {})
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(g_small["w"]), rtol=1e-6)
    out, _, _ = agc.update(g_big, {}, p, {})
    gn = float(np.sqrt(np.sum(np.asarray(out["w"]) ** 2)))
    assert gn == pytest.approx(0.4, rel=1e-5)


def test_scale_per_leaf_patterns_compose():
    tx = chain(scale_per_leaf((("layers", 0.5), ("scale", 0.4))),
               scale_by_lr())
    p = _toy_params()
    u = jax.tree_util.tree_map(jnp.ones_like, p)
    out, _, _ = tx.update(u, tx.init(p), p, {"lr": jnp.float32(2.0)})
    assert float(out["embed"][0, 0]) == pytest.approx(2.0)       # no match
    assert float(out["layers"]["w"][0, 0, 0]) == pytest.approx(1.0)
    # both patterns match layers/scale: 2.0 * 0.5 * 0.4
    assert float(out["layers"]["scale"][0, 0]) == pytest.approx(0.4)


def test_per_leaf_telemetry_vectors_line_up_with_labels():
    from repro.core.telemetry import param_labels, split_metrics
    cfg = OptimizerConfig(telemetry_level="per_leaf")
    tx = build_optimizer(cfg)
    p = _toy_params()
    labels = param_labels(p)
    g = _grads_like(p, 11)
    _, _, tel = tx.update(g, tx.init(p), p,
                          {"lr": jnp.float32(1e-3),
                           "clip_scale": jnp.float32(1.0)})
    scalars, per_leaf = split_metrics(dict(tel))
    assert per_leaf is not None
    for key in ("var_max", "grad_norm", "update_norm", "param_norm",
                "grad_to_weight"):
        assert per_leaf[key].shape == (len(labels),), key
    # scalar keys unpolluted by vectors
    assert all(np.ndim(v) == 0 for v in scalars.values())
    # the per-leaf grad norms recompose into the global norm
    gnorm = float(np.sqrt(np.sum(per_leaf["grad_norm"] ** 2)))
    assert gnorm == pytest.approx(float(scalars["grad_norm"]), rel=1e-5)


# ---------------------------------------------------------------------------
# end to end: per-layer blame under an injected one-block gradient spike
# ---------------------------------------------------------------------------

def _blame_tc(steps, telemetry_level="per_leaf"):
    cfg = reduced(get_arch("gpt2-117m").model).replace(vocab_size=128)
    opt = OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=steps,
                          total_tokens=10 ** 9, schedule="constant",
                          telemetry_level=telemetry_level)
    tc = TrainConfig(model=cfg, optimizer=opt, seq_len=32, global_batch=4,
                     seed=0, eval_interval=0, checkpoint_interval=0)
    return dataclasses.replace(
        tc, regulators=tc.regulators
        + (RegulatorSpec(kind="var_lr_throttle"),))


def test_per_leaf_blame_identifies_injected_layer():
    """The acceptance drill: --inject-faults targeting one block's grads;
    the per-leaf-telemetry-fed throttle must name that block."""
    from repro.distributed.fault_injection import FaultInjector
    from repro.launch.train import train

    class Grab:
        tr = None

        def on_run_start(self, tr):
            Grab.tr = tr

        def on_step_start(self, tr):
            pass

        def on_step_end(self, tr, tele, plan, metrics):
            pass

        def on_run_end(self, tr):
            pass

        def close(self):
            pass

    inj = FaultInjector.from_cli("grad_spike@8:1000|layers/attn", seed=0)
    res = train(_blame_tc(12), fault_injector=inj, hooks=[Grab()])
    assert res.faults_fired == ["grad_spike@8:1000|layers/attn"]
    throttle = Grab.tr.stack["var_lr_throttle"]
    assert throttle.blamed.startswith("layers/attn"), throttle.blamed
    assert throttle.scale < 1.0  # and it actually intervened


# ---------------------------------------------------------------------------
# shampoo preconditioner-staleness telemetry
# ---------------------------------------------------------------------------

def test_shampoo_staleness_telemetry_tracks_refresh_cadence():
    """`shampoo_staleness` counts steps since the last eigh refresh: a
    sawtooth 0..interval-1, resetting on every recompute step."""
    interval = 5
    cfg = OptimizerConfig(optimizer="shampoo", lr=1e-2, weight_decay=0.0,
                          grad_clip=0.0, shampoo_interval=interval)
    p = {"w": jnp.asarray(np.random.RandomState(0).randn(8, 8), jnp.float32)}
    tx = build_optimizer(cfg)
    st = tx.init(p)
    series = []
    for step in range(2 * interval + 2):
        g = _grads_like(p, step)
        p, st, tel = _chain_step(tx, p, g, st, 1e-3)
        assert "shampoo_staleness" in tel
        series.append(int(tel["shampoo_staleness"]))
    assert series == [s % interval for s in range(len(series))]
    # interval=1 refreshes every step: staleness is identically zero
    cfg1 = dataclasses.replace(cfg, shampoo_interval=1)
    tx1 = build_optimizer(cfg1)
    st1 = tx1.init(p)
    for step in range(3):
        p, st1, tel = _chain_step(tx1, p, _grads_like(p, step), st1, 1e-3)
        assert int(tel["shampoo_staleness"]) == 0


def test_adam_chain_has_no_staleness_row():
    cfg = OptimizerConfig(lr=1e-2, weight_decay=0.0, grad_clip=0.0)
    p = _toy_params()
    tx = build_optimizer(cfg)
    _, _, tel = _chain_step(tx, p, _grads_like(p, 1), tx.init(p), 1e-3)
    assert "shampoo_staleness" not in tel


# ---------------------------------------------------------------------------
# runtime per-leaf LR scale (the recovery controller's backoff surface)
# ---------------------------------------------------------------------------

def test_scale_by_lr_runtime_leaf_vector():
    tx = tx_lib.scale_by_lr()
    p = _toy_params()
    u = jax.tree_util.tree_map(jnp.ones_like, p)
    n_leaves = len(jax.tree_util.tree_leaves(u))
    # absent key: the legacy single-scalar trace
    out, _, _ = tx.update(u, tx.init(p), p, {"lr": jnp.float32(2.0)})
    for leaf in jax.tree_util.tree_leaves(out):
        np.testing.assert_allclose(np.asarray(leaf), 2.0)
    # with the vector: each leaf additionally scaled by its entry, in
    # tree_leaves order
    scales = jnp.asarray(np.linspace(0.1, 1.0, n_leaves), jnp.float32)
    out, _, _ = tx.update(u, tx.init(p), p,
                          {"lr": jnp.float32(2.0), "leaf_lr_scale": scales})
    for i, leaf in enumerate(jax.tree_util.tree_leaves(out)):
        np.testing.assert_allclose(np.asarray(leaf), 2.0 * float(scales[i]),
                                   rtol=1e-6)


def test_clip_reports_raw_and_clipped_norms():
    """Satellite regression: `grad_norm` is the raw pre-clip global norm
    (what the noise regulators consume), `grad_norm_clipped` the post-clip
    value — under persistent clipping the clipped norm saturates at the
    limit while the raw norm still varies."""
    tx = tx_lib.clip_global_norm(1.0)
    p = _toy_params()
    raws, clippeds = [], []
    for scale in (4.0, 8.0, 16.0):
        g = jax.tree_util.tree_map(lambda x: scale * jnp.ones_like(x), p)
        _, _, tel = tx.update(g, {}, p, {"clip_scale": jnp.float32(1.0)})
        raws.append(float(tel["grad_norm"]))
        clippeds.append(float(tel["grad_norm_clipped"]))
    assert raws[0] < raws[1] < raws[2]          # raw norm tracks the input
    for c in clippeds:
        assert c == pytest.approx(1.0, rel=1e-5)   # clipped saturates
