"""The grouped matrix product's Pallas kernels
(``ops/pallas/grouped_matmul.py``) in the Pallas interpreter against
``lax.ragged_dot`` and its ``jax.grad``: the product, the rows' cotangent and
the weights' gradient, at widths that are no multiple of 128 nor of the tile
(the analogues of Nemotron-H's 2,688 and 1,856), with an empty group, group
boundaries inside a row tile and rows past the packed ones that hold NaN;
and the tile rule's arithmetic at the shapes of the benchmark's two routed
cells.

float32 on both sides where only the order of the sums differs (1e-5 of the
largest entry); bfloat16 operands against the same float32 reference at
bfloat16's 2**-8.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops.pallas import grouped_matmul as G

# the benchmark's routed cells: (rows_bound, hidden, expert width, held)
NEMOTRON_H = (18432, 2688, 1856, 8)
QWEN3_NEXT = (40960, 2048, 512, 16)

# six row tiles; group 1 is empty, the others start and end inside tiles,
# 300 rows past the packed ones
M, GROUPS = 1536, (300, 0, 700, 236)
# k and n: below a lane block; a multiple of 128; neither that nor the tile
WIDTHS = [(72, 40), (256, 384), (328, 232)]


def operands(k, n, dtype=jnp.float32, m=M, groups=GROUPS, seed=0):
    r = np.random.RandomState(seed)
    return (jnp.asarray(r.randn(m, k), dtype),
            jnp.asarray(r.randn(len(groups), k, n) * 0.1, jnp.float32),
            jnp.asarray(groups, jnp.int32),
            jnp.asarray(r.randn(m, n), jnp.float32))


def ragged(a, w, sizes):
    return jax.lax.ragged_dot(a, w.astype(a.dtype), sizes,
                              preferred_element_type=jnp.float32)


def kernels(a, w, sizes):
    return G.grouped_matmul(a, w, G.row_tiles(sizes, a.shape[0]),
                            interpret=True)


def value_and_gradients(f, a, w, sizes, ct):
    """``f``'s packed rows, and the gradients by ``a`` and ``w`` of its
    packed rows against ``ct``; what lies past the packed rows is dropped
    as ``parallel/moe.py`` drops it."""
    packed = (jnp.arange(a.shape[0]) < jnp.sum(sizes))[:, None]

    def loss(a, w):
        y = jnp.where(packed, f(a, w, sizes), 0.0)
        return jnp.sum(y * ct), y

    (_, y), (da, dw) = jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True)(a, w)
    return y, jnp.where(packed, da, 0).astype(jnp.float32), dw


def close(got, want, rtol):
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=rtol * scale)


def kernels_are_ragged_dots(a, w, sizes, ct, rtol=1e-5):
    got = jax.jit(functools.partial(value_and_gradients, kernels))(
        a, w, sizes, ct)
    want = jax.jit(functools.partial(value_and_gradients, ragged))(
        a, w, sizes, ct)
    for g, t in zip(got, want):
        close(g, t, rtol)


@pytest.mark.parametrize("k,n", WIDTHS)
@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2 ** -8)],
                         ids=["f32", "bf16"])
def test_product_and_gradients_are_ragged_dots(k, n, dtype, rtol):
    a, w, sizes, ct = operands(k, n, dtype)
    assert G.tiles(M, k, n, len(GROUPS), a.dtype.itemsize).rows == G.ROWS
    got = jax.jit(functools.partial(value_and_gradients, kernels))(
        a, w, sizes, ct)
    # bfloat16 operands are held to the float32 product of the same values
    want = jax.jit(functools.partial(value_and_gradients, ragged))(
        a.astype(jnp.float32), w.astype(dtype).astype(jnp.float32), sizes,
        ct.astype(dtype).astype(jnp.float32) if dtype != jnp.float32 else ct)
    assert got[0].dtype == jnp.float32 and got[2].dtype == jnp.float32
    for g, t in zip(got, want):
        close(g, t, rtol)
    assert not np.any(np.asarray(got[2][1]))      # the empty group's


def test_the_rows_cotangent_comes_in_the_rows_dtype():
    a, w, sizes, ct = operands(72, 40, jnp.bfloat16)
    da, dw = jax.jit(jax.grad(lambda a, w: jnp.sum(
        jnp.where((jnp.arange(M) < 1236)[:, None], kernels(a, w, sizes), 0.0)
        * ct), argnums=(0, 1)))(a, w.astype(jnp.bfloat16))
    assert da.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16


def test_nan_past_the_packed_rows_reaches_no_real_row_and_no_weight():
    """Every operand NaN in every row past ``sum(group_sizes)``: the packed
    rows of the product and of the rows' cotangent, and all of the weights'
    gradient, are what they are without and finite."""
    a, w, sizes, ct = operands(328, 232)
    real = sum(GROUPS)
    past = (jnp.arange(M) >= real)[:, None]

    def run(a, w, ct):
        y, back = jax.vjp(lambda a, w: kernels(a, w, sizes), a, w)
        return (y,) + back(ct)

    got = jax.jit(run)(jnp.where(past, jnp.nan, a), w,
                       jnp.where(past, jnp.nan, ct))
    want = jax.jit(run)(a, w, jnp.where(past, 0.0, ct))
    for g, t in zip(got[:2], want[:2]):
        assert np.all(np.isfinite(np.asarray(g[:real])))
        np.testing.assert_array_equal(np.asarray(g[:real]),
                                      np.asarray(t[:real]))
    assert np.all(np.isfinite(np.asarray(got[2])))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))


@pytest.mark.parametrize("groups", [
    (0, 0, 0, 0), (1536, 0, 0, 0), (0, 0, 0, 1536), (512, 512, 0, 512),
    (1, 254, 2, 1000), (0, 1023, 1, 0)],
    ids=["none", "first", "last", "on_tiles", "cut", "one_row"])
def test_visits_cover_every_group_and_every_row_once(groups):
    """``row_tiles``: every group is visited, an empty one once; a group's
    visits are the tiles its rows lie in; visits of one tile follow one
    another; and the product at these sizes is ``ragged_dot``'s."""
    sizes = jnp.asarray(groups, jnp.int32)
    offsets, group, tile, visits = map(np.asarray, G.row_tiles(sizes, M))
    n = int(visits[0])
    assert n <= len(group) == M // G.ROWS + len(groups) - 1
    assert list(offsets) == [0] + list(np.cumsum(groups))
    assert list(group[:n]) == sorted(group[:n])
    assert list(tile[:n]) == sorted(tile[:n])
    for i, size in enumerate(groups):
        mine = tile[:n][group[:n] == i]
        if size:
            lo, hi = offsets[i] // G.ROWS, (offsets[i + 1] - 1) // G.ROWS
            assert list(mine) == list(range(lo, hi + 1))
        else:
            assert len(mine) == 1
    a, w, _, ct = operands(72, 40)
    kernels_are_ragged_dots(a, w, sizes, ct)


def test_rows_that_are_no_whole_tile():
    """``m`` below a row tile, and ``m`` that is no multiple of it: the last
    tile hangs over the edge and what it reads there belongs to no group."""
    for m, groups in [(200, (50, 0, 90, 40)), (1100, (500, 30, 0, 565))]:
        a, w, sizes, ct = operands(72, 40, m=m, groups=groups)
        kernels_are_ragged_dots(a, w, sizes, ct)


def test_a_contraction_too_long_for_vmem_is_tiled_by_a_divisor():
    """``k`` = 4,096 in float32 does not fit whole beside a row tile: the
    product sums over tiles that divide it in its scratch, and the rows'
    cotangent of the transposed problem contracts 4,096 the same way."""
    k, n, m, groups = 4096, 136, 640, (200, 0, 290, 30)
    t = G.tiles(m, k, n, len(groups), 4)
    assert t.product[0] < k and k % t.product[0] == 0
    assert t.product[1] == t.cotangent[0] == n
    assert G.tiles(m, n, k, len(groups), 4).cotangent[0] == t.product[0]
    for kk, nn in ((k, n), (n, k)):
        a, w, sizes, ct = operands(kk, nn, m=m, groups=groups)
        kernels_are_ragged_dots(a, w, sizes, ct)


def held(tm, tk, tn, itemsize, out_itemsize, tiled):
    return 2 * (tm * tk + tk * tn) * itemsize \
        + tm * tn * (2 * out_itemsize + (8 if tiled else 4))


@pytest.mark.parametrize("m,d,f,g", [NEMOTRON_H, QWEN3_NEXT],
                         ids=["nemotron_h", "qwen3_next"])
def test_tile_rule_at_the_benchmarks_shapes(m, d, f, g):
    """Both products of a routed layer (``[m, d] x [g, d, f]`` and ``[m, f]
    x [g, f, d]``) get blocks that are the whole of a dimension or a
    multiple of 128, a contraction that is whole or divided, everything
    inside the VMEM budget by the rule's own arithmetic, and far larger than
    the 512 x 128 x 128 the compiler falls back to at Nemotron-H's widths."""
    rows = G.ROWS
    for k, n in ((d, f), (f, d)):
        t = G.tiles(m, k, n, g, 2)
        assert t is not None and t.rows == rows
        for (tk, tn), (kk, nn), out in ((t.product, (k, n), 4),
                                        (t.cotangent, (n, k), 2)):
            assert kk % tk == 0 and (tk == kk or tk % 128 == 0)
            assert tn == nn or tn % 128 == 0
            assert held(rows, tk, tn, 2, out, tk != kk) <= G._VMEM_BUDGET
            assert rows * tk * tn >= 30 * 512 * 128 * 128
            # a group's weights stay in VMEM from one row tile to the next
            assert tk == kk
        tk, tn = t.gradient
        assert (tk == k or tk % 128 == 0) and (tn == n or tn % 128 == 0)
        assert 3 * rows * (tk + tn) * 2 + 3 * tk * tn * 4 <= G._VMEM_BUDGET
        assert rows * tk * tn >= 15 * 512 * 128 * 128
    assert G._VMEM_BUDGET < 16 * 1024 * 1024
    assert G._PARAMS.vmem_limit_bytes is None


def test_tile_rule_is_a_function_of_the_shapes():
    up = G.tiles(*NEMOTRON_H[:3], NEMOTRON_H[3], 2)
    assert up == G.Tiles(256, (2688, 640), (1856, 896), (896, 640))
    assert G.tiles(18432, 1856, 2688, 8, 2) == G.Tiles(
        256, (1856, 896), (2688, 640), (640, 896))
    # rows below a tile are one tile
    assert G.tiles(200, 72, 40, 4, 4).rows == 200
    # a contraction that is no multiple of 128 and does not fit whole
    assert G.tiles(18432, 128 * 1000 + 64, 256, 8, 2) is None
    with pytest.raises(ValueError, match="no tiles"):
        G.grouped_matmul(jnp.zeros((512, 128064), jnp.bfloat16),
                         jnp.zeros((2, 128064, 256)), None)
