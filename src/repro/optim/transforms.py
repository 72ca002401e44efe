"""Composable optimizer chain: optax-shaped gradient transforms, no optax.

The monolithic ``adamw_update`` becomes a chain of
:class:`GradientTransform`\\ s — ``init(params) -> state`` /
``update(updates, state, params, hyper) -> (updates, state, telemetry)``
pairs — so clipping, preconditioning, weight decay, per-leaf LR scaling and
telemetry collection compose instead of forking the train step.  ``hyper``
carries the runtime scalars (``lr``, ``clip_scale``) so regulators keep
retuning steps without recompiles.

Sign convention: the chain produces the quantity *subtracted* from the
params (:func:`apply_updates` does ``p - u``), matching the legacy
``p - lr * step``.  The default chain —

    clip_global_norm -> scale_by_adam -> add_decayed_weights -> scale_by_lr

— reproduces the legacy AdamW trajectory *numerically exactly* (params,
opt state, and scalar telemetry), which is pinned by
``tests/test_optim_chain.py``; everything else (SM3, Shampoo-grafted,
adaptive gradient clipping, per-leaf LR scales, per-leaf telemetry) is
opt-in through :class:`~repro.configs.base.OptimizerConfig` and
assembled by :func:`build_optimizer`.

Chain state is a dict keyed by transform name (``{"adam": {"m", "v",
"count"}, ...}``) so checkpoints stay path-addressable; stateless
transforms contribute an empty dict (zero checkpoint leaves), and
``repro.checkpoint`` restores legacy ``{"m","v","count"}`` payloads into
the ``adam`` slot via key aliasing (see ``checkpoint.restore``).
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import OptimizerConfig
from repro.core.stability import momentum_stats, variance_stats
from repro.core.telemetry import leaf_norms, leaf_var_max, param_labels

Hyper = Dict[str, jax.Array]
Telemetry = Dict[str, jax.Array]


class GradientTransform(NamedTuple):
    """One chain link.  ``update`` must be jit-traceable."""

    name: str
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any, Hyper], Tuple[Any, Any, Telemetry]]


def chain(*transforms: GradientTransform) -> GradientTransform:
    """Compose transforms left to right; state is keyed by transform name."""
    names = [t.name for t in transforms]
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate transform names in chain: {names}")

    def init(params):
        return {t.name: t.init(params) for t in transforms}

    def update(updates, state, params, hyper):
        new_state, telemetry = {}, {}
        for t in transforms:
            updates, st, tel = t.update(updates, state[t.name], params, hyper)
            new_state[t.name] = st
            telemetry.update(tel)
        return updates, new_state, telemetry

    return GradientTransform("chain", init, update)


def apply_updates(params: Any, updates: Any) -> Any:
    """``p - u`` in fp32, cast back to the param dtype (legacy semantics)."""
    return jax.tree_util.tree_map(
        lambda p, u: (p - u).astype(p.dtype), params, updates)


def abstract_chain_state(tx: GradientTransform, params_shapes: Any) -> Any:
    """ShapeDtypeStruct tree of the chain state (checkpoint ``like`` trees,
    sharding derivation) without materializing arrays."""
    return jax.eval_shape(tx.init, params_shapes)


def _zeros_like_tree(t: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, jnp.float32), t)


# ---------------------------------------------------------------------------
# clipping
# ---------------------------------------------------------------------------

def clip_global_norm(max_norm: float, per_leaf_telemetry: bool = False
                     ) -> GradientTransform:
    """Cast to fp32, measure the global norm, clip to ``max_norm *
    hyper["clip_scale"]``.  ``max_norm <= 0`` measures without clipping
    (so ``grad_norm`` telemetry survives an AGC-only configuration).

    Telemetry contract: ``grad_norm`` is the RAW pre-clip norm (measured
    on the incoming gradients, before any scaling) — the noise/variance
    signal regulators act on.  ``grad_norm_clipped`` is the post-clip
    norm (``gnorm * scale``); under sustained clipping it saturates at
    the limit, which is exactly why nothing downstream may regulate on
    it."""

    def update(updates, state, params, hyper):
        leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
                  for x in jax.tree_util.tree_leaves(updates)]
        gnorm = jnp.sqrt(sum(leaves))
        if max_norm > 0:
            limit = max_norm * hyper["clip_scale"]
            scale = jnp.minimum(1.0, limit / jnp.maximum(gnorm, 1e-12))
        else:
            scale = jnp.float32(1.0)
        out = jax.tree_util.tree_map(
            lambda g: (g.astype(jnp.float32) * scale), updates)
        tel: Telemetry = {"grad_norm": gnorm,
                          "grad_norm_clipped": gnorm * scale}
        if per_leaf_telemetry:
            tel["leaf_grad_norm"] = jnp.sqrt(jnp.stack(leaves))
        return out, state, tel

    return GradientTransform("clip", lambda params: {}, update)


def adaptive_grad_clip(clipping: float, eps: float = 1e-3
                       ) -> GradientTransform:
    """AGC (Brock et al.): per-leaf clip of the grad-norm/param-norm ratio.
    Composes after (or replaces, with ``grad_clip=0``) the global clip."""

    def update(updates, state, params, hyper):
        def one(g, p):
            pn = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
            gn = jnp.sqrt(jnp.sum(jnp.square(g)))
            limit = clipping * jnp.maximum(pn, eps)
            return g * jnp.minimum(1.0, limit / jnp.maximum(gn, 1e-6))

        return (jax.tree_util.tree_map(one, updates, params), state, {})

    return GradientTransform("agc", lambda params: {}, update)


# ---------------------------------------------------------------------------
# preconditioners
# ---------------------------------------------------------------------------

def scale_by_adam(cfg: OptimizerConfig, per_leaf_telemetry: bool = False
                  ) -> GradientTransform:
    """The legacy Adam core, bit-for-bit: m/v EMAs, bias correction,
    ``mhat / (sqrt(vhat) + eps)``, plus the paper's variance telemetry."""
    b1, b2, eps = cfg.beta1, cfg.beta2, cfg.eps

    def init(params):
        return {"m": _zeros_like_tree(params), "v": _zeros_like_tree(params),
                "count": jnp.zeros((), jnp.int32)}

    def update(updates, state, params, hyper):
        count = state["count"] + 1
        cf = count.astype(jnp.float32)
        bc1 = 1.0 - b1 ** cf
        bc2 = 1.0 - b2 ** cf
        new_m = jax.tree_util.tree_map(
            lambda m, g: b1 * m + (1.0 - b1) * g, state["m"], updates)
        new_v = jax.tree_util.tree_map(
            lambda v, g: b2 * v + (1.0 - b2) * jnp.square(g),
            state["v"], updates)
        out = jax.tree_util.tree_map(
            lambda m, v: (m / bc1) / (jnp.sqrt(v / bc2) + eps), new_m, new_v)
        tel = {**variance_stats(new_v), **momentum_stats(new_m)}
        if per_leaf_telemetry:
            tel["leaf_var_max"] = leaf_var_max(new_v)
        return out, {"m": new_m, "v": new_v, "count": count}, tel

    return GradientTransform("adam", init, update)


def scale_by_sm3(cfg: OptimizerConfig, per_leaf_telemetry: bool = False
                 ) -> GradientTransform:
    """SM3 (Anil et al.): per-dimension min/max accumulators instead of a
    full second-moment tree — O(sum of dims) memory per leaf instead of
    O(prod of dims) — with optional heavy-ball momentum on the
    preconditioned update.  The variance telemetry reduces the *estimated*
    second moment (the min-broadcast of the accumulators), so regulators
    see the same ``var_max`` series shape as Adam."""
    b1, eps = cfg.sm3_momentum, cfg.eps

    def leaf_accs(x):
        if x.ndim == 0:
            return (jnp.zeros((), jnp.float32),)
        return tuple(
            jnp.zeros(tuple(d if i == axis else 1
                            for i, d in enumerate(x.shape)), jnp.float32)
            for axis in range(x.ndim))

    def init(params):
        leaves = jax.tree_util.tree_leaves(params)
        st = {"acc": tuple(leaf_accs(x) for x in leaves)}
        if b1 > 0:
            st["m"] = _zeros_like_tree(params)
        return st

    def update(updates, state, params, hyper):
        leaves, treedef = jax.tree_util.tree_flatten(updates)
        new_accs: List[Tuple[jax.Array, ...]] = []
        nus: List[jax.Array] = []
        outs: List[jax.Array] = []
        for g, accs in zip(leaves, state["acc"]):
            est = accs[0]
            for a in accs[1:]:
                est = jnp.minimum(est, a)
            nu = est + jnp.square(g)
            if g.ndim == 0:
                new_accs.append((nu,))
            else:
                new_accs.append(tuple(
                    jnp.max(nu, axis=tuple(i for i in range(g.ndim)
                                           if i != axis), keepdims=True)
                    for axis in range(g.ndim)))
            nus.append(nu)
            outs.append(g / (jnp.sqrt(nu) + eps))
        out = jax.tree_util.tree_unflatten(treedef, outs)
        new_state = {"acc": tuple(new_accs)}
        tel = variance_stats(nus)
        if b1 > 0:
            new_m = jax.tree_util.tree_map(
                lambda m, u: b1 * m + (1.0 - b1) * u, state["m"], out)
            new_state["m"] = new_m
            out = new_m
            tel.update(momentum_stats(new_m))
        if per_leaf_telemetry:
            tel["leaf_var_max"] = leaf_var_max(nus)
        return out, new_state, tel

    return GradientTransform("sm3", init, update)


def _inv_pth_root(s: jax.Array, p: float, eps: float) -> jax.Array:
    """Symmetric inverse p-th root via eigendecomposition (fp32; batched
    over leading dims)."""
    n = s.shape[-1]
    w, v = jnp.linalg.eigh(s + eps * jnp.eye(n, dtype=s.dtype))
    w = jnp.maximum(w, eps) ** (-1.0 / p)
    return jnp.einsum("...ij,...j,...kj->...ik", v, w, v)


def scale_by_shampoo(cfg: OptimizerConfig, per_leaf_telemetry: bool = False
                     ) -> GradientTransform:
    """Shampoo-style block-diagonal preconditioning grafted onto the Adam
    update magnitude.

    Each eligible leaf (ndim >= 2, last two dims <= ``shampoo_block_size``)
    is viewed as a stack of (rows, cols) blocks over its leading dims — one
    block per scan-stacked layer slice, i.e. genuinely block-diagonal —
    with decayed L/R Kronecker statistics and inverse-4th-root
    preconditioners recomputed every ``shampoo_interval`` steps.  The
    preconditioned direction is rescaled per block to the norm of the Adam
    update (grafting), so the step-size trajectory stays on the well-tuned
    Adam scale while the *direction* gains curvature information.
    Ineligible leaves fall back to the plain Adam update.
    """
    adam = scale_by_adam(cfg, per_leaf_telemetry=per_leaf_telemetry)
    beta, eps = cfg.beta2, cfg.shampoo_eps
    block, interval = cfg.shampoo_block_size, max(cfg.shampoo_interval, 1)

    def eligible(x) -> bool:
        return x.ndim >= 2 and x.shape[-2] <= block and x.shape[-1] <= block

    def leaf_stats(x):
        if not eligible(x):
            return None
        lead = math.prod(x.shape[:-2]) if x.ndim > 2 else 1
        r, c = x.shape[-2], x.shape[-1]
        eye_r = jnp.broadcast_to(jnp.eye(r, dtype=jnp.float32), (lead, r, r))
        eye_c = jnp.broadcast_to(jnp.eye(c, dtype=jnp.float32), (lead, c, c))
        return {"l": jnp.zeros((lead, r, r), jnp.float32),
                "r": jnp.zeros((lead, c, c), jnp.float32),
                "pl": eye_r, "pr": eye_c}

    def init(params):
        leaves = jax.tree_util.tree_leaves(params)
        return {"adam": adam.init(params),
                "stats": tuple(leaf_stats(x) for x in leaves)}

    def update(updates, state, params, hyper):
        adam_u, adam_state, tel = adam.update(updates, state["adam"],
                                              params, hyper)
        count = adam_state["count"]
        recompute = (count - 1) % interval == 0
        g_leaves, treedef = jax.tree_util.tree_flatten(updates)
        a_leaves = jax.tree_util.tree_leaves(adam_u)
        new_stats, outs = [], []
        for g, au, st in zip(g_leaves, a_leaves, state["stats"]):
            if st is None:
                new_stats.append(None)
                outs.append(au)
                continue
            shape = g.shape
            gb = g.reshape((-1,) + shape[-2:])
            l_new = beta * st["l"] + (1.0 - beta) * jnp.einsum(
                "bij,bkj->bik", gb, gb)
            r_new = beta * st["r"] + (1.0 - beta) * jnp.einsum(
                "bji,bjk->bik", gb, gb)
            pl = jax.lax.cond(recompute,
                              lambda ln=l_new: _inv_pth_root(ln, 4.0, eps),
                              lambda pl=st["pl"]: pl)
            pr = jax.lax.cond(recompute,
                              lambda rn=r_new: _inv_pth_root(rn, 4.0, eps),
                              lambda pr=st["pr"]: pr)
            precond = jnp.einsum("bij,bjk,bkl->bil", pl,
                                 gb.astype(jnp.float32), pr)
            ab = au.reshape((-1,) + shape[-2:])
            a_norm = jnp.sqrt(jnp.sum(jnp.square(ab), axis=(-2, -1),
                                      keepdims=True))
            p_norm = jnp.sqrt(jnp.sum(jnp.square(precond), axis=(-2, -1),
                                      keepdims=True))
            grafted = precond * (a_norm / jnp.maximum(p_norm, 1e-16))
            new_stats.append({"l": l_new, "r": r_new, "pl": pl, "pr": pr})
            outs.append(grafted.reshape(shape))
        out = jax.tree_util.tree_unflatten(treedef, outs)
        # preconditioner staleness: steps since the last eigh refresh.
        # The recompute flag keys off the shared Adam count, so every
        # block refreshes on the same cadence and one scalar covers all
        # of them (bench_optim surfaces it per arm).
        tel = dict(tel, shampoo_staleness=((count - 1) % interval)
                   .astype(jnp.float32))
        return out, {"adam": adam_state, "stats": tuple(new_stats)}, tel

    return GradientTransform("shampoo", init, update)


# ---------------------------------------------------------------------------
# decay / scaling / telemetry tails
# ---------------------------------------------------------------------------

def decay_mask_tree(params: Any, mode: str) -> Any:
    """Which leaves get weight decay.  ``all`` is the legacy behavior
    (every leaf, biases and norm scales included); ``std`` is the standard
    mask — only matrices decay, 1-D/scalar leaves (biases, norm gains) do
    not.  The model zoo stacks per-layer leaves on a leading scan axis
    under the top-level ``layers`` key, so a stacked bias arrives as
    ``(L, d)``: the mask strips that axis before counting effective dims,
    and a leaf is a matrix when >= 2 of the remaining dims have size > 1."""
    if mode == "all":
        return jax.tree_util.tree_map(lambda p: True, params)
    if mode != "std":
        raise ValueError(f"unknown decay_mask {mode!r} (all | std)")

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)

    def is_matrix(path, p) -> bool:
        shape = p.shape
        if path and "layers" in str(getattr(path[0], "key", path[0])):
            shape = shape[1:]  # scan-stacked: drop the layer axis
        return len([d for d in shape if d > 1]) >= 2

    return jax.tree_util.tree_unflatten(
        treedef, [is_matrix(path, p) for path, p in flat])


def add_decayed_weights(weight_decay: float, mask_mode: str = "all"
                        ) -> GradientTransform:
    """``u + weight_decay * p`` on masked leaves (decoupled decay, applied
    before the LR scale — exactly where the legacy fused update put it)."""

    def update(updates, state, params, hyper):
        if weight_decay == 0.0:
            return updates, state, {}
        mask = decay_mask_tree(params, mask_mode)
        out = jax.tree_util.tree_map(
            lambda u, p, m: u + weight_decay * p if m else u,
            updates, params, mask)
        return out, state, {}

    return GradientTransform("decay", lambda params: {}, update)


def scale_per_leaf(lr_scales: Tuple[Tuple[str, float], ...]
                   ) -> GradientTransform:
    """Per-leaf LR scaling: each ``(pattern, factor)`` multiplies the
    update of every leaf whose label contains ``pattern`` (factors
    compose multiplicatively when several patterns match)."""

    def update(updates, state, params, hyper):
        labels = param_labels(updates)
        leaves, treedef = jax.tree_util.tree_flatten(updates)
        out = []
        for label, u in zip(labels, leaves):
            factor = 1.0
            for pattern, f in lr_scales:
                if pattern in label:
                    factor *= f
            out.append(u * factor if factor != 1.0 else u)
        return jax.tree_util.tree_unflatten(treedef, out), state, {}

    return GradientTransform("leaf_lr", lambda params: {}, update)


def per_leaf_update_telemetry() -> GradientTransform:
    """Final-update / param norms per leaf (placed after decay, before the
    LR scale, so the vector is the step *direction* magnitude)."""

    def update(updates, state, params, hyper):
        return updates, state, {"leaf_update_norm": leaf_norms(updates),
                                "leaf_param_norm": leaf_norms(params)}

    return GradientTransform("leaf_tel", lambda params: {}, update)


def scale_by_lr() -> GradientTransform:
    """Final LR scale.  ``hyper["leaf_lr_scale"]`` — optional, a
    ``(n_leaves,)`` runtime vector in ``tree_leaves`` order — additionally
    multiplies each leaf's update: the recovery controller's per-layer LR
    backoff surface.  Key *presence* is a trace-time (Python) check, so
    callers that never pass it keep the legacy single-scalar trace
    byte-identical."""

    def update(updates, state, params, hyper):
        lr = hyper["lr"]
        scales = hyper.get("leaf_lr_scale")
        if scales is None:
            return (jax.tree_util.tree_map(lambda u: lr * u, updates),
                    state, {})
        leaves, treedef = jax.tree_util.tree_flatten(updates)
        out = [lr * scales[i].astype(u.dtype) * u
               for i, u in enumerate(leaves)]
        return jax.tree_util.tree_unflatten(treedef, out), state, {}

    return GradientTransform("lr", lambda params: {}, update)


# ---------------------------------------------------------------------------
# config -> chain
# ---------------------------------------------------------------------------

def build_optimizer(cfg: OptimizerConfig) -> GradientTransform:
    """Assemble the chain an :class:`OptimizerConfig` describes.  With
    default fields this is exactly the legacy AdamW path."""
    per_leaf = cfg.telemetry_level == "per_leaf"
    ts: List[GradientTransform] = [
        clip_global_norm(cfg.grad_clip, per_leaf_telemetry=per_leaf)]
    if cfg.agc_clip > 0:
        ts.append(adaptive_grad_clip(cfg.agc_clip, cfg.agc_eps))
    if cfg.optimizer == "adamw":
        ts.append(scale_by_adam(cfg, per_leaf_telemetry=per_leaf))
    elif cfg.optimizer == "sm3":
        ts.append(scale_by_sm3(cfg, per_leaf_telemetry=per_leaf))
    elif cfg.optimizer == "shampoo":
        ts.append(scale_by_shampoo(cfg, per_leaf_telemetry=per_leaf))
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r} "
                         f"(adamw | sm3 | shampoo)")
    ts.append(add_decayed_weights(cfg.weight_decay, cfg.decay_mask))
    if cfg.lr_scales:
        ts.append(scale_per_leaf(cfg.lr_scales))
    if per_leaf:
        ts.append(per_leaf_update_telemetry())
    ts.append(scale_by_lr())
    return chain(*ts)


def migrate_opt_state(opt: Dict[str, Any]) -> Dict[str, Any]:
    """Upgrade a legacy in-memory ``{"m","v","count"}`` opt state into the
    default-chain format (``{"clip": {}, "adam": {...}, ...}``).  Already-
    migrated states pass through unchanged."""
    if "m" in opt and "v" in opt and "count" in opt:
        return {"clip": {}, "adam": {"m": opt["m"], "v": opt["v"],
                                     "count": opt["count"]},
                "decay": {}, "lr": {}}
    return opt
