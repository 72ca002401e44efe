"""Differentiable grouped matrix product over the experts a chip holds.

``gmm(lhs, rhs, group_sizes, start)``: ``lhs`` (m, k) holds rows sorted by
expert, ``group_sizes`` (E,) counts the rows of each of the layer's E
experts, and ``rhs`` (n_local, k, n) holds the weights of experts
``start .. start + n_local - 1``.  Returns (m, n): each held expert's rows
times its weights, zeros elsewhere.  The backward pass computes the
gradient of ``lhs`` with ``moe_gmm`` against the transposed weights and the
gradient of ``rhs`` with ``moe_tgmm``, both over the held experts' rows
only (``kernel.py``).  Rows are padded to the kernels' row tile here;
tiles come from ``kernel.tiling``.  Off a TPU the kernels run in interpret
mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.moe_gmm import kernel as K


def _row_tile(m: int) -> int:
    return K.BLOCK_M if m >= K.BLOCK_M else -(-m // 8) * 8


def _pad_rows(x: jax.Array, tm: int) -> jax.Array:
    pad = (-x.shape[0]) % tm
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _gmm(lhs, rhs, group_sizes, start, transpose_rhs, interpret):
    m = lhs.shape[0]
    tm = _row_tile(m)
    k, n = ((rhs.shape[2], rhs.shape[1]) if transpose_rhs
            else rhs.shape[1:])
    tk, tn = K.tiling(k, n, lhs.dtype.itemsize)
    out = K.gmm(_pad_rows(lhs, tm), rhs, group_sizes, start, tm=tm, tk=tk,
                tn=tn, transpose_rhs=transpose_rhs, interpret=interpret)
    return out[:m]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm_vjp(lhs, rhs, group_sizes, start, interpret):
    return _gmm(lhs, rhs, group_sizes, start, False, interpret)


def _fwd(lhs, rhs, group_sizes, start, interpret):
    return (_gmm(lhs, rhs, group_sizes, start, False, interpret),
            (lhs, rhs, group_sizes, start))


def _bwd(interpret, res, dout):
    lhs, rhs, group_sizes, start = res
    dlhs = _gmm(dout, rhs, group_sizes, start, True, interpret)
    tm = _row_tile(lhs.shape[0])
    k, n = rhs.shape[1:]
    tk, tn = K.tiling(k, n, rhs.dtype.itemsize, transposed_out=True)
    drhs = K.tgmm(_pad_rows(lhs, tm), _pad_rows(dout, tm), group_sizes,
                  start, rhs.shape[0], tm=tm, tk=tk, tn=tn,
                  interpret=interpret)
    return dlhs, drhs, None, None


_gmm_vjp.defvjp(_fwd, _bwd)


def gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
        start, *, interpret: bool | None = None) -> jax.Array:
    """(m, k) rows sorted by expert times the held experts' (n_local, k, n)
    weights; ``start`` is the first held expert's id."""
    return _gmm_jit(lhs, rhs, group_sizes.astype(jnp.int32),
                    jnp.asarray(start, jnp.int32),
                    resolve_interpret(interpret))


# jitted whole, as the flash wrapper is, so that the kernels keep their own
# names in the compiled program whatever transformation wraps the call
@functools.partial(jax.jit, static_argnums=(4,))
def _gmm_jit(lhs, rhs, group_sizes, start, interpret):
    return _gmm_vjp(lhs, rhs, group_sizes, start, interpret)
