"""The plain reference against the program at a small size on the CPU,
from the same seeded weights: loss, gradients and one AdamW step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import program, weights
from bench.harness.cell import load_cell
from bench.reference import common
from bench.tests.fixture import make_root


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return load_cell(make_root(tmp_path_factory.mktemp("ref")),
                     "tiny.tiny-slw")


def _program_model(cell):
    from repro.models import model_zoo
    return model_zoo.build_model(program.model_config(cell),
                                 dtype=jnp.float32, remat="none")


def test_weights_fit_the_program_tree(cell):
    from repro.models import model_zoo
    got = jax.tree_util.tree_map(lambda x: x.shape,
                                 weights.make(3, cell))
    want = jax.tree_util.tree_map(
        lambda x: x.shape,
        model_zoo.abstract_params(program.model_config(cell)))
    assert got == want


def test_weights_depend_on_every_bit_of_the_seed(cell):
    a = weights.make(5, cell)["embed"]
    b = weights.make(5 + 2**33, cell)["embed"]
    assert not np.allclose(np.asarray(a), np.asarray(b))


def test_loss_and_gradients_match_the_program(cell):
    d = cell.dims
    params = weights.make(7, cell)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, d.vocab, (3, 33), dtype=np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    model = _program_model(cell)
    with jax.default_matmul_precision("highest"):
        (lp, _), gp = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch)
    r = cell.arch("reference").Reference(d, rows_per_block=2)
    lr, gr = r.loss_and_grad(params, batch["tokens"], batch["labels"])
    assert abs(float(lp) - lr) < 1e-5 * lr
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-6)


def test_adamw_step_matches_the_program_optimizer(cell):
    from repro.configs.base import OptimizerConfig
    from repro.optim import transforms as tx
    params = weights.make(8, cell)
    grads = jax.tree_util.tree_map(lambda p: 3.0 * jnp.sin(p), params)
    cfg = OptimizerConfig()
    chain = tx.build_optimizer(cfg)
    upd, _, _ = chain.update(grads, chain.init(params), params,
                             {"lr": 1e-3, "clip_scale": 1.0})
    want = tx.apply_updates(params, upd)
    got, _, _ = common.adamw_step(params, grads, common.adamw_init(params),
                                  lr=1e-3, clip=cfg.grad_clip, b1=cfg.beta1,
                                  b2=cfg.beta2, eps=cfg.eps,
                                  weight_decay=cfg.weight_decay)
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)
