"""A grouped matrix product as Pallas TPU kernels whose tiles follow the
widths they are given, joined by a ``jax.custom_vjp``.

``a`` ``[m, k]`` holds the rows of ``g`` groups one after the other,
``group_sizes[i]`` rows of group ``i``; ``w`` ``[g, k, n]`` holds a matrix a
group.  ``grouped_matmul`` is ``lax.ragged_dot(a, w, group_sizes)``: row ``r``
of group ``i`` times ``w[i]``.  Rows at or past ``sum(group_sizes)`` are left
as they lie in memory, in the result and in the rows' cotangent, and are
never read into a sum.  ``lax.ragged_dot`` stays the definition (the tests
hold the kernels to it and to its ``jax.grad``) and is what runs wherever
these kernels do not (``parallel/moe.py`` asks ``tiles``).

Why the repo has its own: the TPU compiler makes ``ragged_dot`` a kernel of
this very design, but takes its tiles from a table that knows multiples of
512 and 256 only.  At Nemotron-H's widths, 2,688 = 21 x 128 and 1,856 =
14.5 x 128, it falls back to 512 x 128 x 128 tiles (the ``ragged_dot_tiling``
attribute of the deviceless v5e compile): a grid step of 0.085 us of work
under 0.5 us of stepping, 5.8 % of the products' roofline (PERF.md, PR 34).
Here a block may span a whole dimension whatever its width, or be a multiple
of 128 whose last tile hangs over the edge, so every width gets large tiles.

The design is the stock one (the compiler's, and ``megablox`` in
``jax.experimental.pallas.ops.tpu``):

  * ``row_tiles`` lists, once for all the products of a layer, the VISITS: a
    row tile of ``ROWS`` rows with a group that has rows in it, in order.  A
    tile that a group boundary cuts is visited once a group; an empty group
    visits one tile and finds no row its own.  The lists go in as scalar
    prefetch and the grid's visit axis is as long as the list is FULL, so
    tiles past the packed rows cost nothing;
  * the product (``_gmm``; the rows' cotangent is the same kernel with the
    weights' block contracted over its last dimension): grid ``(n tiles,
    visits, k tiles)``.  The contraction is tiled only by a multiple of 128
    that divides it, else taken whole: an overhanging tile would read what
    lies past the edge into the sum.  Where it is whole (every product of
    both benchmark cells) a group's weights stay in VMEM from one row tile
    to the next.  A visit stores the rows of its own group only, so the
    second visit of a cut tile keeps what the first wrote;
  * the weights' gradient (``_tgmm``): grid ``(k tiles, n tiles, visits)``,
    ``a_g^T dy_g`` summed in the float32 result block over a group's visits,
    rows that are not the group's replaced by zeros in BOTH operands (what
    lies past the packed rows may be NaN, and ``0 * NaN`` is NaN);
  * operands in ``a.dtype`` (bfloat16 in training; a float32 cotangent is
    cast first, which is the one-pass bfloat16 contraction the compiler's
    kernel makes of it), float32 accumulation, the result float32, the rows'
    cotangent in ``a.dtype`` and the weights' gradient accumulated in float32
    and handed back in ``w.dtype``.

All inside the 16 MiB of VMEM an operation may scope by default (PERF.md,
PR 26: a kernel that asks for more takes it from its neighbours' prefetch).
Kernel playbook: /opt/skills/guides/pallas_guide.md.
"""
import functools
import typing

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# rows of a row tile.  One for every product, so that a layer's visits are
# listed once.  A tile that a group boundary cuts is visited once a group,
# so the smaller tile wastes fewer rows: at Nemotron-H's 768 rows a group a
# product takes 0.452 ms at 256 against 0.557 at 512, the weights' gradient,
# whose steps halve, 0.81 against 0.76 (PERF.md, PR 34)
ROWS = 256
# what a kernel may hold in VMEM by the reckoning of ``tiles``: the largest
# block of the benchmark's cells reckons 11.6 MB and compiles inside the
# default 16 MiB with the compiler's own temporaries
_VMEM_BUDGET = 12 * 1024 * 1024
# the tile rule's prices, in bytes of HBM traffic: a FLOP (a v5e moves a byte
# while it multiplies and adds 197e12 / 819e9 = 240 times) and a grid step
# (0.35 us of stepping)
_BYTES_PER_FLOP = 1 / 240
_BYTES_PER_STEP = 0.35e-6 * 819e9
_NN = (((1,), (0,)), ((), ()))    # (m, k) x (k, n) -> (m, n)
_NT = (((1,), (1,)), ((), ()))    # (m, k) x (n, k) -> (m, n)
_TN = (((0,), (0,)), ((), ()))    # (m, k) x (m, n) -> (k, n)
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


class Tiles(typing.NamedTuple):
    """``(contraction block, result block)`` of the product and of the rows'
    cotangent, ``(k block, n block)`` of the weights' gradient."""
    rows: int
    product: tuple
    cotangent: tuple
    gradient: tuple


def _rows(m):
    """Rows of a row tile of ``m`` rows: ``ROWS``, or all of ``m`` where it
    is less."""
    return min(ROWS, m)


def _blocks(dim, divide):
    """The blocks a dimension of ``dim`` may be cut into: the whole of it,
    and the multiples of 128 below it (only those that divide it where
    ``divide``: a contraction takes no overhanging tile)."""
    return [dim] + [t for t in range(_LANES, dim, _LANES)
                    if not divide or dim % t == 0]


def _product_tiles(tm, visits, k, n, g, itemsize, out_itemsize):
    """``(tk, tn)`` of ``[m, k] x [g, k, n]`` with the least price, or
    ``None`` where no block fits.  VMEM: the rows' and the weights' blocks
    double-buffered, the result's block double-buffered and once more as the
    product before it is stored (twice more where the contraction is tiled:
    the float32 scratch it is summed in).  HBM: the rows once an n tile; the
    weights once where the contraction is whole (a group's block stays from
    one visit to the next), else once a visit; the result once."""
    best = None
    for tk in _blocks(k, divide=True):
        for tn in _blocks(n, divide=False):
            tiles_k, tiles_n = k // tk, -(-n // tn)
            held = 2 * (tm * tk + tk * tn) * itemsize \
                + tm * tn * (2 * out_itemsize + (4 if tiles_k == 1 else 8))
            if held > _VMEM_BUDGET:
                continue
            wide = tiles_n * tn
            price = 2 * visits * tm * k * wide * _BYTES_PER_FLOP \
                + visits * tm * k * tiles_n * itemsize \
                + k * wide * (g if tiles_k == 1 else visits) * itemsize \
                + visits * tm * n * out_itemsize \
                + tiles_n * visits * tiles_k * _BYTES_PER_STEP
            if best is None or price < best[0]:
                best = (price, tk, tn)
    return best and best[1:]


def _gradient_tiles(tm, visits, k, n, g, itemsize):
    """``(tk, tn)`` of ``[m, k]^T x [m, n] -> [g, k, n]`` with the least
    price, or ``None``.  VMEM: both operands' blocks double-buffered and once
    more with the neighbours' rows zeroed, the float32 result's block
    double-buffered and once more as the product before it is added.  HBM:
    the left operand once an n tile, the right once a k tile, the result
    once."""
    best = None
    for tk in _blocks(k, divide=False):
        for tn in _blocks(n, divide=False):
            tiles_k, tiles_n = -(-k // tk), -(-n // tn)
            held = 3 * tm * (tk + tn) * itemsize + 3 * tk * tn * 4
            if held > _VMEM_BUDGET:
                continue
            price = 2 * visits * tm * tiles_k * tk * tiles_n * tn \
                * _BYTES_PER_FLOP \
                + visits * tm * (k * tiles_n + n * tiles_k) * itemsize \
                + g * k * n * 4 \
                + tiles_k * tiles_n * visits * _BYTES_PER_STEP
            if best is None or price < best[0]:
                best = (price, tk, tn)
    return best and best[1:]


@functools.lru_cache(maxsize=None)
def tiles(m, k, n, g, itemsize=2):
    """The ``Tiles`` of ``[m, k] x [g, k, n]`` and its two backward products
    for operands of ``itemsize`` bytes, or ``None`` where the compiled
    kernels do not take the shapes: a function of the shapes alone.  Row
    tiles of ``ROWS`` (all of ``m`` where it is less); every other block the
    cheapest that fits ``_VMEM_BUDGET`` by ``_product_tiles`` and
    ``_gradient_tiles``, reckoned for the most visits the shapes allow."""
    tm = _rows(m)
    visits = -(-m // tm) + g - 1
    found = Tiles(
        tm,
        _product_tiles(tm, visits, k, n, g, itemsize, 4),
        _product_tiles(tm, visits, n, k, g, itemsize, itemsize),
        _gradient_tiles(tm, visits, k, n, g, itemsize))
    return found if all(found) else None


def row_tiles(group_sizes, m):
    """The visits of ``m`` rows in groups of ``group_sizes`` ``[g]`` int32
    (they add up to at most ``m``), for every product over these rows:
    ``(offsets [g + 1], group [V], tile [V], visits [1])``, all int32.  Group
    ``i`` holds the rows ``offsets[i] ... offsets[i + 1]``; visit ``v <
    visits[0]`` is row tile ``tile[v]`` for group ``group[v]``.  Groups come
    in order and a group's tiles in order, so the visits of one tile follow
    one another; an empty group visits the tile its offset lies in.  ``V =
    tiles + g - 1`` is the most there can be."""
    tm = _rows(m)
    g = group_sizes.shape[0]
    last_tile = -(-m // tm) - 1
    most = last_tile + g
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    count = jnp.where(group_sizes > 0,
                      (ends - 1) // tm - starts // tm + 1, 1)
    upto = jnp.cumsum(count)
    group = jnp.repeat(jnp.arange(g, dtype=jnp.int32), count,
                       total_repeat_length=most)
    tile = (starts // tm)[group] + jnp.arange(most, dtype=jnp.int32) \
        - (upto - count)[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (offsets.astype(jnp.int32), group,
            jnp.clip(tile, 0, last_tile).astype(jnp.int32),
            upto[-1:].astype(jnp.int32))


def _mine(offsets, groups, tiles_, v, shape):
    """Which entries of a ``shape`` block of row tile ``tiles_[v]`` lie in a
    row of group ``groups[v]``."""
    group = groups[v]
    row = lax.add(lax.broadcasted_iota(jnp.int32, shape, 0),
                  lax.mul(tiles_[v], shape[0]))
    return lax.bitwise_and(lax.ge(row, offsets[group]),
                           lax.lt(row, offsets[lax.add(group, 1)]))


def _gmm_kernel(offsets, groups, tiles_, a_ref, w_ref, o_ref, *acc,
                dims, tiles_k):
    v, k_i = pl.program_id(1), pl.program_id(2)
    part = lax.dot_general(a_ref[...], w_ref[...], dims,
                           preferred_element_type=jnp.float32)

    def store(total):
        # the group's own rows only: a cut tile's other rows were stored by
        # the visit before or will be by the next
        o_ref[...] = lax.select(
            _mine(offsets, groups, tiles_, v, total.shape),
            lax.convert_element_type(total, o_ref.dtype), o_ref[...])

    if tiles_k == 1:
        store(part)
        return
    acc, = acc

    @pl.when(lax.eq(k_i, 0))
    def _():
        acc[...] = part

    @pl.when(lax.gt(k_i, 0))
    def _():
        acc[...] = lax.add(acc[...], part)

    @pl.when(lax.eq(k_i, tiles_k - 1))
    def _():
        store(acc[...])


def _gmm(a, w, meta, block, out_dtype, transposed, interpret):
    """``a`` ``[m, k]`` times ``w`` ``[g, k, n]`` (``transposed``: ``[g, n,
    k]``, contracted over its last dimension) by groups, ``[m, n]`` in
    ``out_dtype``; ``block`` is ``(tk, tn)``."""
    (m, k), n = a.shape, w.shape[1 if transposed else 2]
    tm, (tk, tn) = _rows(m), block
    tiles_k = k // tk
    *scalars, visits = meta
    return pl.pallas_call(
        functools.partial(_gmm_kernel, dims=_NT if transposed else _NN,
                          tiles_k=tiles_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(-(-n // tn), visits[0], tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, v, i, o, gr, t: (t[v], i)),
                pl.BlockSpec((None, tn, tk) if transposed else (None, tk, tn),
                             (lambda j, v, i, o, gr, t: (gr[v], j, i))
                             if transposed else
                             (lambda j, v, i, o, gr, t: (gr[v], i, j)))],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, i, o, gr, t: (t[v], j)),
            scratch_shapes=[] if tiles_k == 1
            else [pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(*scalars, a, w)


def _tgmm_kernel(offsets, groups, tiles_, a_ref, dy_ref, o_ref):
    v = pl.program_id(2)
    group = groups[v]
    # rows of the neighbouring groups, and whatever lies past the packed
    # rows, as zeros in both operands
    a = lax.select(_mine(offsets, groups, tiles_, v, a_ref.shape),
                   a_ref[...], lax.full(a_ref.shape, 0, a_ref.dtype))
    dy = lax.select(_mine(offsets, groups, tiles_, v, dy_ref.shape),
                    dy_ref[...], lax.full(dy_ref.shape, 0, dy_ref.dtype))
    part = lax.dot_general(a, dy, _TN, preferred_element_type=jnp.float32)
    # groups come in order: the visits of one follow one another, and its
    # result block stays in VMEM until the last has been added
    first = lax.bitwise_or(lax.eq(v, 0),
                           lax.ne(groups[lax.max(lax.sub(v, 1), 0)], group))

    @pl.when(first)
    def _():
        o_ref[...] = part

    @pl.when(lax.bitwise_not(first))
    def _():
        o_ref[...] = lax.add(o_ref[...], part)


def _tgmm(a, dy, meta, g, block, interpret):
    """``a_g^T dy_g`` for each group's rows ``a_g`` of ``a`` ``[m, k]`` and
    ``dy_g`` of ``dy`` ``[m, n]``: ``[g, k, n]`` float32; ``block`` is
    ``(tk, tn)``."""
    (m, k), n = a.shape, dy.shape[1]
    tm, (tk, tn) = _rows(m), block
    *scalars, visits = meta
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(-(-k // tk), -(-n // tn), visits[0]),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda i, j, v, o, gr, t: (t[v], i)),
                pl.BlockSpec((tm, tn),
                             lambda i, j, v, o, gr, t: (t[v], j))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda i, j, v, o, gr, t: (gr[v], i, j))),
        out_shape=jax.ShapeDtypeStruct((g, k, n), jnp.float32),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(*scalars, a, dy)


@functools.lru_cache(maxsize=None)
def _make(found, w_dtype, interpret):
    def fwd(a, w, meta):
        w = w.astype(a.dtype)
        out = _gmm(a, w, meta, found.product, jnp.float32, False, interpret)
        return out, (a, w, meta)

    @jax.custom_vjp
    def product(a, w, meta):
        return fwd(a, w, meta)[0]

    def bwd(res, dy):
        a, w, meta = res
        dy = dy.astype(a.dtype)
        da = _gmm(dy, w, meta, found.cotangent, a.dtype, True, interpret)
        dw = _tgmm(a, dy, meta, w.shape[0], found.gradient, interpret)
        return da, dw.astype(w_dtype), None

    product.defvjp(fwd, bwd)
    return product


def grouped_matmul(a, w, meta, interpret=False):
    """``lax.ragged_dot(a, w.astype(a.dtype), group_sizes,
    preferred_element_type=float32)`` for ``a`` ``[m, k]``, ``w`` ``[g, k,
    n]`` and ``meta = row_tiles(group_sizes, m)``, through the kernels, at
    shapes ``tiles`` takes; differentiable in ``a`` and ``w``.  ``interpret``
    runs them in the Pallas interpreter (the tests' way to them on a CPU)."""
    (m, k), (g, _, n) = a.shape, w.shape
    found = tiles(m, k, n, g, jnp.dtype(a.dtype).itemsize)
    if found is None:
        raise ValueError(f"no tiles for [{m}, {k}] x [{g}, {k}, {n}]")
    return _make(found, jnp.dtype(w.dtype), bool(interpret))(a, w, meta)
