"""LFM2's gated short convolution, the mix ``y = C * conv(B * u)`` of
``models/lfm2.py:ShortConv``, as two Pallas TPU kernels joined by a
``jax.custom_vjp``.

The equations are the XLA form's (``models/lfm2.py:_gated_conv_xla``, the
definition and the CPU's path): ``v = b * u`` in float32, the causal
depthwise taps ``z_t = sum_i w_i v_{t-(K-1)+i}`` with zeros before the first
position, ``y = c * z`` in float32, one cast to ``b.dtype``.  Backward, with
``g = dy * c``: ``dc = dy * z``, ``dv_t = sum_i w_i g_{t+(K-1)-i}``,
``db = dv * u``, ``du = dv * b`` and ``dw_i = sum_t g_t v_{t-(K-1)+i}``.

What the XLA form pays for and these do not: its float32 ``pad`` and
shifted slices are passes over HBM of their own, its ``jax.checkpoint`` runs
the forward once more inside the backward pass, and the taps' gradient is a
reduction over every position that reads the inputs a second time.  Here the
forward kernel reads ``b``, ``c``, ``u`` once and writes ``y`` once; the
backward kernel reads ``b``, ``c``, ``u``, ``dy`` once, makes the forward
again in VMEM, writes ``db``, ``dc``, ``du`` once and sums ``dw`` on the way.
The residuals are the inputs, what the checkpoint kept.

  * a program owns ``TS`` positions by ``TC`` channels of one sequence (grid
    ``(batch, channel blocks, sequence blocks)``).  The stencil's neighbours
    across a block's border come in as a second block spec of ``_HALO`` rows
    on the same array: the rows before the block (``b``, ``u``) and,
    backward, the rows after it (``c``, ``dy``); at the sequence's ends they
    are masked to zero;
  * inside, the block is walked in strips of ``_STRIP`` rows by ``_LANES``
    lanes, so that a strip's float32 values stay in vector registers.  A
    strip takes the eight rows before it (after it, backward) from the block
    or the halo, and a tap's shift is a static slice of the strip laid
    beside them;
  * ``dw`` is summed in float32 over a program's positions, eight rows at a
    time, then over a sequence's blocks in the result block (the sequence
    axis walks in order, backward), and handed out as ``[batch, K, C]``
    partial sums that XLA adds up.

In the LFM2 cell's step on a v5e (``[4, 8192, 2048]``, three taps) a forward
call takes 0.81 ms and a backward call 1.45 ms, about 660 and 650 GB/s of
the chip's 819 (PERF.md section 6).

Both stay inside the 16 MiB of VMEM an operation may scope by default (a
kernel that asks for more takes it from its neighbours' prefetch, PERF.md
section 6); ``tiles`` reckons the backward kernel's blocks against
``_VMEM_BUDGET``.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_SUB = 8        # a float32 tile's sublanes: the rows a strip borrows
_HALO = 16      # rows of a halo block: a bfloat16 tile's sublanes
_STRIP = 32     # rows a loop turn
_BLOCK_LANES = (512, 256, 128)
_BLOCK_ROWS = (512, 256, 128, 64, 32)
# what the backward kernel may hold in VMEM by the reckoning of ``tiles``
_VMEM_BUDGET = 12 * 1024 * 1024

_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel"))
# the backward kernel sums the taps' gradient over a sequence's blocks
_SUMMED = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _f32(x):
    return x.astype(jnp.float32)


def _lead(ref, halo_ref, r, lanes):
    """The ``_SUB`` rows before strip ``r`` of the block, float32: from the
    block, or from the last rows of the halo before it for the first
    strip."""
    start = pl.multiple_of(jnp.maximum(r * _STRIP - _HALO, 0), _HALO)
    inside = ref[0, pl.ds(start, _HALO), lanes]
    return _f32(jnp.where(r == 0, halo_ref[0, :, lanes], inside))[-_SUB:]


def _trail(ref, halo_ref, r, last, lanes):
    """The ``_SUB`` rows after strip ``r`` of the block, float32: from the
    block, or from the first rows of the halo after it for the last strip
    (``last``)."""
    start = pl.multiple_of(jnp.minimum((r + 1) * _STRIP,
                                       ref.shape[1] - _HALO), _HALO)
    inside = ref[0, pl.ds(start, _HALO), lanes]
    return _f32(jnp.where(r == last, halo_ref[0, :, lanes], inside))[:_SUB]


def _taps(x, w, shift):
    """``sum_i w_i x[shift(i) : shift(i) + _STRIP]`` over the taps, in the
    taps' order; ``w`` is ``(K, lanes)`` float32."""
    z = None
    for i in range(w.shape[0]):
        at = shift(i)
        term = x[at:at + _STRIP] * w[i:i + 1]
        z = term if z is None else z + term
    return z


def _fold(x):
    """``(_STRIP, lanes)`` -> ``(_SUB, lanes)``: the sum of its row tiles."""
    return jnp.sum(x.reshape(_STRIP // _SUB, _SUB, x.shape[1]), axis=0)


def _past(k):
    """Where tap ``i`` reads in a strip laid after ``_SUB`` earlier rows."""
    return lambda i: _SUB - (k - 1) + i


def _columns(tc, body, carry=0):
    """``body(lanes, carry)`` over the 128-lane columns of a block."""
    def column(j, carry):
        return body(pl.ds(pl.multiple_of(j * _LANES, _LANES), _LANES), carry)

    return jax.lax.fori_loop(0, tc // _LANES, column, carry)


def _fwd_kernel(w_ref, b_ref, u_ref, c_ref, bh_ref, uh_ref, y_ref):
    k = w_ref.shape[0]
    start = pl.program_id(2) == 0

    def column(lanes, carry):
        w = w_ref[:, lanes]

        def turn(r, carry):
            at = pl.ds(pl.multiple_of(r * _STRIP, _STRIP), _STRIP)
            v = _f32(b_ref[0, at, lanes]) * _f32(u_ref[0, at, lanes])
            before = (_lead(b_ref, bh_ref, r, lanes)
                      * _lead(u_ref, uh_ref, r, lanes))
            before = jnp.where(start & (r == 0), 0.0, before)
            z = _taps(jnp.concatenate([before, v]), w, _past(k))
            y_ref[0, at, lanes] = (_f32(c_ref[0, at, lanes]) * z).astype(
                y_ref.dtype)
            return carry

        return jax.lax.fori_loop(0, b_ref.shape[1] // _STRIP, turn, carry)

    _columns(b_ref.shape[2], column)


def _bwd_kernel(w_ref, b_ref, u_ref, c_ref, dy_ref, bh_ref, uh_ref, ch_ref,
                dyh_ref, db_ref, dc_ref, du_ref, dw_ref):
    k = w_ref.shape[0]
    turns = b_ref.shape[1] // _STRIP
    start = pl.program_id(2) == 0
    end = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(start)
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def column(lanes, carry):
        w = w_ref[:, lanes]

        def turn(r, acc):
            at = pl.ds(pl.multiple_of(r * _STRIP, _STRIP), _STRIP)
            b, u = _f32(b_ref[0, at, lanes]), _f32(u_ref[0, at, lanes])
            c, dy = _f32(c_ref[0, at, lanes]), _f32(dy_ref[0, at, lanes])
            before = (_lead(b_ref, bh_ref, r, lanes)
                      * _lead(u_ref, uh_ref, r, lanes))
            before = jnp.where(start & (r == 0), 0.0, before)
            after = (_trail(c_ref, ch_ref, r, turns - 1, lanes)
                     * _trail(dy_ref, dyh_ref, r, turns - 1, lanes))
            after = jnp.where(end & (r == turns - 1), 0.0, after)
            v = jnp.concatenate([before, b * u])
            g = jnp.concatenate([dy * c, after])
            z = _taps(v, w, _past(k))
            dv = _taps(g, w, lambda i: k - 1 - i)
            dc_ref[0, at, lanes] = (dy * z).astype(dc_ref.dtype)
            db_ref[0, at, lanes] = (dv * u).astype(db_ref.dtype)
            du_ref[0, at, lanes] = (dv * b).astype(du_ref.dtype)
            gs, ahead = g[:_STRIP], _past(k)
            return [a + _fold(gs * v[ahead(i):ahead(i) + _STRIP])
                    for i, a in enumerate(acc)]

        zero = jnp.zeros((_SUB, _LANES), jnp.float32)
        acc = jax.lax.fori_loop(0, turns, turn, [zero] * k)
        dw_ref[0, :, lanes] += jnp.concatenate(
            [jnp.sum(a, axis=0, keepdims=True) for a in acc])
        return carry

    _columns(b_ref.shape[2], column)


def _specs(ts, tc, s):
    """Block specs for a grid ``(batch, channel blocks, sequence blocks)``:
    a ``(TS, TC)`` block of ``[B, S, C]``, the ``_HALO`` rows before it and
    the ``_HALO`` rows after it (clamped at the sequence's ends, where the
    kernels mask them), and the taps' columns."""
    per, halos = ts // _HALO, s // _HALO
    return (pl.BlockSpec((1, ts, tc), lambda b, j, t: (b, t, j)),
            pl.BlockSpec((1, _HALO, tc),
                         lambda b, j, t: (b, jnp.maximum(t * per - 1, 0), j)),
            pl.BlockSpec((1, _HALO, tc),
                         lambda b, j, t: (b, jnp.minimum((t + 1) * per,
                                                         halos - 1), j)))


def _taps_spec(k, tc):
    return pl.BlockSpec((k, tc), lambda b, j, t: (0, j))


# jitted: the call sites of a step (layers, recomputation) share one trace and
# one lowering of each kernel
@functools.partial(jax.jit, static_argnames=("ts", "tc", "interpret"))
def _forward(b, c, u, w, *, ts, tc, interpret):
    bsz, s, ch = b.shape
    block, before, _ = _specs(ts, tc, s)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(bsz, ch // tc, s // ts),
        in_specs=[_taps_spec(w.shape[0], tc), block, block, block, before,
                  before],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(b.shape, b.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(w, b, u, c, b, u)


@functools.partial(jax.jit, static_argnames=("ts", "tc", "interpret"))
def _backward(b, c, u, w, dy, *, ts, tc, interpret):
    bsz, s, ch = b.shape
    k = w.shape[0]
    block, before, after = _specs(ts, tc, s)
    grads = [jax.ShapeDtypeStruct(b.shape, x.dtype) for x in (b, c, u)]
    return pl.pallas_call(
        _bwd_kernel,
        grid=(bsz, ch // tc, s // ts),
        in_specs=[_taps_spec(k, tc), block, block, block, block, before,
                  before, after, after],
        out_specs=[block, block, block,
                   pl.BlockSpec((1, k, tc), lambda b, j, t: (b, 0, j))],
        out_shape=grads + [jax.ShapeDtypeStruct((bsz, k, ch), jnp.float32)],
        compiler_params=_SUMMED,
        interpret=interpret,
    )(w, b, u, c, dy, b, u, c, dy)


@functools.lru_cache(maxsize=None)
def _make(ts, tc, interpret):
    kw = dict(ts=ts, tc=tc, interpret=interpret)

    @jax.custom_vjp
    def mix(b, c, u, w):
        return _forward(b, c, u, w, **kw)

    def fwd(b, c, u, w):
        return _forward(b, c, u, w, **kw), (b, c, u, w)

    def bwd(res, dy):
        db, dc, du, dw = _backward(*res, dy.astype(res[0].dtype), **kw)
        return db, dc, du, jnp.sum(dw, axis=0).astype(res[3].dtype)

    mix.defvjp(fwd, bwd)
    return mix


def _vmem_bytes(ts, tc, k, itemsize):
    """The backward kernel's reckoning: four input blocks, four halos, the
    taps, three result blocks and the taps' partial sums, each
    double-buffered, and a strip's float32 work."""
    block, halo = ts * tc * itemsize, _HALO * tc * itemsize
    taps = k * tc * 4
    return (2 * (7 * block + 4 * halo + 2 * taps)
            + 16 * (_STRIP + _SUB) * _LANES * 4)


def tiles(b, s, c, k, itemsize=2):
    """``(TS, TC)``, the blocks of positions and channels the kernels take
    for ``[b, s, c]`` with ``k`` taps, or ``None`` where they do not serve
    the shapes: channels of whole 128-lane tiles, at most ``_SUB + 1`` taps
    (a strip borrows ``_SUB`` rows), and positions in whole strips of a
    block of at least ``_HALO`` rows, inside ``_VMEM_BUDGET``."""
    del b
    if c % _LANES or not 1 <= k <= _SUB + 1:
        return None
    tc = next(t for t in _BLOCK_LANES if c % t == 0)
    for ts in _BLOCK_ROWS:
        if s % ts == 0 and _vmem_bytes(ts, tc, k, itemsize) <= _VMEM_BUDGET:
            return ts, tc
    return None


def gated_short_conv(b, c, u, w):
    """``(c * conv(b * u)).astype(b.dtype)`` through the kernels: ``b``,
    ``c``, ``u`` ``[B, S, C]`` in the model's dtype, ``w`` ``[K, C]``
    float32 taps.  The shapes must be ones ``tiles`` takes.  (The tests run
    the kernels in the Pallas interpreter through ``_make``.)"""
    found = tiles(*b.shape, w.shape[0], jnp.dtype(b.dtype).itemsize)
    if found is None:
        raise ValueError(f"no blocks for {b.shape} with {w.shape[0]} taps")
    return _make(*found, False)(b, c, u, w)
