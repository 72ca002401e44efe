"""Grouped products over a share of the experts (``kernels/moe_gmm``), in
interpret mode, against plain ``jnp``: uneven groups, empty groups, groups
that share a row tile, and a share that starts past the first expert."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_gmm import kernel as K
from repro.kernels.moe_gmm.ops import gmm
from repro.kernels.moe_gmm.ref import gmm_reference

# float32 products over at most 256 terms of unit normals
TOL = 2e-4


def _case(sizes, k, n, n_local, extra_rows=0, seed=0):
    sizes = jnp.asarray(sizes, jnp.int32)
    m = int(sizes.sum()) + extra_rows
    r = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(r[0], (m, k))
    w = jax.random.normal(r[1], (n_local, k, n))
    dy = jax.random.normal(r[2], (m, n))
    return sizes, x, w, dy


def _rows_of(sizes, g):
    off = np.concatenate([[0], np.cumsum(np.asarray(sizes))])
    return slice(int(off[g]), int(off[g + 1]))


# (group sizes, first held group, groups held); tiles of 128 rows
CASES = [
    ([100, 0, 170, 3, 0, 129, 300, 0, 40, 0], 1, 6),   # empty, shared tiles
    ([0, 0, 256, 256, 0], 0, 5),                        # tile-aligned groups
    ([37, 91, 0, 5, 200, 11], 3, 3),                    # the last groups
    ([64, 64, 64, 64], 2, 1),                           # one group held
    ([0, 0, 0, 7], 0, 3),                               # nothing routed here
]


@pytest.mark.parametrize("sizes,start,n_local", CASES)
def test_gmm_kernel_matches_jnp(sizes, start, n_local):
    s, x, w, _ = _case(sizes, 256, 384, n_local, extra_rows=3)
    pad = (-x.shape[0]) % 128
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    want = gmm_reference(xp, w, s, start)
    got = K.gmm(xp, w, s, jnp.int32(start), tm=128, tk=128, tn=128,
                interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL,
                               rtol=TOL)
    got_t = K.gmm(xp, jnp.swapaxes(w, 1, 2), s, jnp.int32(start), tm=128,
                  tk=128, tn=128, transpose_rhs=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(want),
                               atol=TOL, rtol=TOL)


@pytest.mark.parametrize("sizes,start,n_local", CASES)
def test_tgmm_kernel_matches_jnp(sizes, start, n_local):
    s, x, w, dy = _case(sizes, 256, 384, n_local, extra_rows=3)
    pad = (-x.shape[0]) % 128
    xp, dyp = (jnp.pad(a, ((0, pad), (0, 0))) for a in (x, dy))
    got = K.tgmm(xp, dyp, s, jnp.int32(start), n_local, tm=128, tk=128,
                 tn=128, interpret=True)
    for j in range(n_local):
        rows = _rows_of(s, start + j)
        want = np.asarray(x)[rows].T @ np.asarray(dy)[rows]
        np.testing.assert_allclose(np.asarray(got[j]), want, atol=TOL,
                                   rtol=TOL)


def test_gmm_gradients_match_jnp():
    """The wrapper's own tiles and row padding, and its backward pass
    (``moe_gmm`` for the rows, ``moe_tgmm`` for the weights)."""
    s, x, w, dy = _case([5, 0, 17, 3, 0, 9, 30, 0], 64, 48, 4, extra_rows=6)
    y = gmm(x, w, s, 2, interpret=True)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(gmm_reference(x, w, s, 2)),
                               atol=TOL, rtol=TOL)

    def loss(fn):
        return lambda a, b: jnp.sum(fn(a, b) * dy)

    got = jax.grad(loss(lambda a, b: gmm(a, b, s, 2, interpret=True)),
                   (0, 1))(x, w)
    want = jax.grad(loss(lambda a, b: gmm_reference(a, b, s, 2)),
                    (0, 1))(x, w)
    for name, a, b in zip(("dx", "dw"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=TOL,
                                   rtol=TOL, err_msg=name)


def test_group_metadata_walks_only_the_held_groups():
    """The grid has one step per row tile of each held group (a tile two
    groups share is walked by both), and none for the other groups."""
    sizes = jnp.asarray([100, 0, 170, 3, 0, 129, 300, 0], jnp.int32)
    (offsets, groups, tiles), n = K.group_metadata(
        sizes, 768, 128, jnp.int32(2), 4, visit_empty=False)
    # group 2: rows 100-269 (tiles 0-2); 3: 270-272 (tile 2); 4 empty;
    # 5: 273-401 (tiles 2-3)
    assert int(n) == 3 + 1 + 0 + 2
    assert np.asarray(groups)[:6].tolist() == [2, 2, 2, 3, 5, 5]
    assert np.asarray(tiles)[:6].tolist() == [0, 1, 2, 2, 2, 3]
    assert np.asarray(offsets).tolist() == [0, 100, 100, 270, 273, 273,
                                            402, 702, 702]
    # tgmm must zero an empty held group: it gets a step of its own
    _, n_empty = K.group_metadata(sizes, 768, 128, jnp.int32(2), 4,
                                  visit_empty=True)
    assert int(n_empty) == int(n) + 1


def test_tiling_fits_the_budget_at_moonlight_widths():
    """d_model 2048, expert width 1408: the forward's, the row gradient's
    and the weight gradient's tiles divide their dimensions and fit."""
    for k, n in ((2048, 1408), (1408, 2048)):
        for transposed in (False, True):
            tk, tn = K.tiling(k, n, 4, transposed_out=transposed)
            assert k % tk == 0 and n % tn == 0
            per = (2 * 4 * K.BLOCK_M * (tk + tn) + 12 * tk * tn
                   if transposed else
                   2 * 4 * (K.BLOCK_M * tk + tk * tn) + 12 * K.BLOCK_M * tn)
            assert per <= K.VMEM_BUDGET
