"""The program's DeepSeek-V3 block (latent attention, leading dense layer,
dropless expert-share MoE with sigmoid-plus-bias routing) against the
plain reference ``bench/reference/deepseek_v3.py`` on seeded random
weights, on the CPU at a small size: d 64, 4 heads, latent 32, 16 routed
experts of which 4 are held here, vocabulary 512, 64 tokens."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.programs import deepseek_v3 as program  # noqa: E402
from bench.reference import deepseek_v3 as ref  # noqa: E402
from repro.models import model_zoo, moe  # noqa: E402
from repro.models.moe import moe_ffn_dropless  # noqa: E402

SMALL = {
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
    "published": {"n_routed_experts": 16},
}
RUN = {"attn_backend": "flash_interpret", "moe_dispatch": "dropless"}

# The program against the reference in float32 on the CPU reads gaps of
# at most ~5e-7 (loss, relative) and ~5e-7 (each gradient leaf's
# difference over its norm).  The limits leave 20x and 200x of room; a
# routing rule other than the reference's moves a routed token's output
# by O(1) and reads 1e-3 or more in the loss and 1e-1 in the gradients.
LOSS_TOL = 1e-5
GRAD_TOL = 1e-4


def _dims(**over):
    return ref.dims_from_config({**SMALL, **over})


def _params(d, seed=0):
    shapes = ref.weight_shapes(d)
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(flat))
    leaves = []
    for key, (path, shape) in zip(keys, flat):
        mean, std = ref.weight_init("/".join(str(p.key) for p in path), d)
        leaves.append(mean + std * jax.random.normal(key, shape))
    return jax.tree_util.tree_unflatten(tree, leaves)


def _batch(seed=1, rows=2, seq=64):
    t = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, 512)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


def _program(d, **over):
    cfg = program.model_config("small", d, RUN).replace(**over)
    return model_zoo.build_model(cfg, dtype=jnp.float32, remat="full")


def _gaps(params, d, batch, prog_params=None, **over):
    """(loss gap, worst leaf gap) of the program against the reference."""
    model = _program(d, **over)
    (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
        params if prog_params is None else prog_params, batch)
    r_loss, r_grads = ref.Reference(d).loss_and_grad(
        params, np.asarray(batch["tokens"]), np.asarray(batch["labels"]))
    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(r_grads)):
        nb = float(jnp.linalg.norm(b))
        if nb:
            worst = max(worst, float(jnp.linalg.norm(a - b)) / nb)
    return abs(float(loss) - r_loss) / r_loss, worst, metrics


@pytest.fixture(scope="module")
def small():
    d = _dims()
    return d, _params(d), _batch()


def test_program_tree_is_the_reference_tree(small):
    d, params, _ = small
    defs = model_zoo.abstract_params(_program(d).cfg)
    assert (jax.tree_util.tree_structure(defs)
            == jax.tree_util.tree_structure(params))
    assert [x.shape for x in jax.tree_util.tree_leaves(defs)] == \
        [x.shape for x in jax.tree_util.tree_leaves(params)]


def test_loss_and_every_gradient_match_the_reference(small):
    d, params, batch = small
    loss_gap, grad_gap, metrics = _gaps(params, d, batch)
    assert loss_gap <= LOSS_TOL and grad_gap <= GRAD_TOL
    # 2 MoE layers x 128 tokens x 6 choices, a quarter of them held here
    # on average
    assert 0 < float(metrics["moe_held_rows"]) < 2 * 128 * 6
    assert float(metrics["moe_load_max"]) >= 1.0


def test_a_program_that_ignores_the_bias_fails(small):
    d, params, batch = small
    blind = jax.tree_util.tree_map(lambda x: x, params)
    blind["layers"]["moe"]["router_bias"] = jnp.zeros_like(
        params["layers"]["moe"]["router_bias"])
    loss_gap, grad_gap, _ = _gaps(params, d, batch, prog_params=blind)
    assert loss_gap > LOSS_TOL or grad_gap > GRAD_TOL
    assert grad_gap > 100 * GRAD_TOL


def _softmax_route(params, xt, cfg):
    """The planted fault: top-k of the softmax, bias unread."""
    logits = jnp.dot(xt, params["router"],
                     precision=jax.lax.Precision.HIGHEST)
    gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    gate = gate / gate.sum(-1, keepdims=True)
    return gate * cfg.routed_scaling_factor, idx


def test_a_program_with_softmax_routing_fails(small, monkeypatch):
    d, params, batch = small
    monkeypatch.setattr(moe, "route", _softmax_route)
    loss_gap, grad_gap, _ = _gaps(params, d, batch)
    assert grad_gap > 100 * GRAD_TOL


@pytest.mark.parametrize("field", [{"n_held_experts": 4},
                                   {"expert_offset": 4},
                                   {"routed_scaling_factor": 2.446}],
                         ids=["held", "offset", "scaling"])
def test_expert_share_fields_need_the_dropless_dispatch(field):
    """The capacity dispatches read none of the expert-share fields: a
    config that sets one of them with such a dispatch is refused."""
    plain = {"n_held_experts": 0, "expert_offset": 0,
             "routed_scaling_factor": 1.0}
    _program(_dims(), moe_dispatch="row_local", **plain)
    with pytest.raises(ValueError, match="dropless"):
        _program(_dims(), moe_dispatch="row_local", **{**plain, **field})


def _layer_params(d, seed=3):
    """One MoE layer's parameters (the reference's tree, unstacked)."""
    return jax.tree_util.tree_map(lambda x: x[0],
                                  _params(d, seed)["layers"]["moe"])


def test_the_shares_add_up_to_the_whole_layer():
    """Four chips of four experts each: their routed parts, plus the
    shared experts counted once, are the uncut layer over all 16."""
    whole = _dims(n_routed_experts=16, expert_offset=0)
    p = _layer_params(whole)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 64, 64))
    want = ref._experts(x, p, whole, "float32")
    shared = ref._experts(x, dict(p, w_down=jnp.zeros_like(p["w_down"])),
                          whole, "float32")
    total = shared
    for j in range(4):
        d = _dims(expert_offset=4 * j)
        cfg = _program(d).cfg
        part = dict(p, **{w: p[w][4 * j:4 * j + 4]
                          for w in ("w_gate", "w_up", "w_down")})
        y, aux = moe_ffn_dropless(part, x, cfg)
        total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("pull", [10.0, -10.0], ids=["all", "none"])
def test_no_assignment_is_dropped_at_any_load(pull):
    """A bias that draws every token to the four held experts (each of the
    128 tokens chooses all four: 512 rows here, past the window) or away
    from them (no row here, inside it); the program computes every held
    row and matches the reference."""
    d = _dims()
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, 64))
    p = _layer_params(d)
    bias = jnp.zeros(16).at[4:8].set(pull)
    p = dict(p, router_bias=bias)
    y, aux = moe_ffn_dropless(p, x, _program(d).cfg)
    assert float(aux["held_rows"]) == (512.0 if pull > 0 else 0.0)
    # 512 rows overflow the 256-row window: they take two
    assert float(aux["window"]) == (0.0 if pull > 0 else 1.0)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref._experts(x, p, d, "float32")),
                               atol=1e-5, rtol=1e-5)


def _layer_grads(p, x, dy, fn):
    """The layer's output and its gradients for ``x`` and every weight."""
    def loss(x, p):
        y = fn(x, p)
        return jnp.sum(y * dy), y
    (_, y), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(x, p)
    return y, jax.tree_util.tree_leaves(grads)


def _leaf_gap(a, b):
    nb = float(jnp.linalg.norm(b))
    return float(jnp.linalg.norm(a - b)) / nb if nb else float(
        jnp.linalg.norm(a))


@pytest.mark.parametrize("offset,pull,fits", [(4, 0.0, True),
                                              (12, 0.0, True),
                                              (4, 10.0, False)],
                         ids=["middle", "last", "two-windows"])
def test_the_window_matches_every_row_and_the_reference(offset, pull, fits,
                                                        monkeypatch):
    """At a load that fits the held experts' window (128 tokens x 6 over
    16 experts, 4 held: 192 rows expected, a window of 256), the layer and
    its gradients match the path over every assignment and the float32
    reference.  Held last, the window runs past the sorted assignments
    into the padding.  A bias that draws every token to the held experts
    (512 rows) takes two windows, and matches as well."""
    d = _dims(expert_offset=offset)
    cfg = _program(d).cfg
    assert moe.window_rows(128, 6, 4, 16) == 256
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 64, 64))
    dy = jax.random.normal(jax.random.PRNGKey(8), x.shape)
    p = _layer_params(d)
    p = dict(p, router_bias=jnp.zeros(16).at[offset:offset + 4].set(pull))
    _, aux = moe_ffn_dropless(p, x, cfg)
    assert float(aux["window"]) == float(fits)
    assert (0 < float(aux["held_rows"]) <= 256) == fits

    y, grads = _layer_grads(
        p, x, dy, lambda x, p: moe_ffn_dropless(p, x, cfg)[0])
    want_y, want = _layer_grads(
        p, x, dy, lambda x, p: ref._experts(x, p, d, "float32"))
    with monkeypatch.context() as m:
        m.setattr(moe, "window_rows", lambda *shape: None)
        full_y, full = _layer_grads(
            p, x, dy, lambda x, p: moe_ffn_dropless(p, x, cfg)[0])
    np.testing.assert_allclose(np.asarray(y), np.asarray(full_y),
                               atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               atol=1e-5, rtol=1e-5)
    assert len(grads) == len(full) == len(want) == 1 + len(
        jax.tree_util.tree_leaves(p))
    for a, b, c in zip(grads, full, want):
        assert _leaf_gap(a, b) <= 1e-6
        assert _leaf_gap(a, c) <= GRAD_TOL


def test_the_window_exists_only_below_the_most_rows_held(small):
    """The window is the expected held rows with a quarter of room, in
    whole row tiles, and only where that is short of every assignment
    the held experts could take; a step of the model counts the MoE
    layers that took it."""
    # Moonlight's one-chip share: 8192 tokens x 6, 8 of 64 experts held
    assert moe.window_rows(8192, 6, 8, 64) == 7680
    # every expert held, or a load no larger than one tile: no window
    assert moe.window_rows(8192, 6, 64, 64) is None
    assert moe.window_rows(8, 6, 8, 64) is None
    d, params, batch = small
    _, metrics = _program(d).loss(params, batch)
    assert float(metrics["moe_window_share"]) == 1.0


def test_latent_attention_refuses_to_serve(small):
    d, params, batch = small
    model = _program(d)
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        model.prefill(params, {"tokens": batch["tokens"]})
    with pytest.raises(NotImplementedError, match="latent KV cache"):
        model.decode(params, {"pos": jnp.int32(0)}, batch["tokens"][:, :1])
