"""Mamba-2's chunked selective scan (``ops/ssd.py`` has the equations) as two
Pallas TPU kernels joined by a ``jax.custom_vjp``.

What the scan form pays for and these do not: every intermediate of a chunk
(the decay tile ``L``, ``C B^T``, the masked product, each chunk's own state)
is a tensor in HBM there, the float32 state of every head goes through HBM at
every chunk, the block layout is a copy of the inputs, and the forward runs
once more inside the block checkpoint.  Here a program owns one group of one
sequence, the ``H / G`` heads that share the group's ``B`` and ``C``, and
walks that sequence's chunks in order (the last grid axis, sequential); the
``[H / G * P, N]`` float32 state of its heads stays in VMEM from the first
chunk to the last.  A grid step is a block of ``SPAN`` chunks; only ``y`` and
the state at each block's start leave the forward kernel, and those states
with the inputs are all the backward pass keeps.

  * ``u``, ``B`` and ``C`` are read, and their cotangents written, as
    column blocks of the mixer's convolved channels ``[B, S, H * P + 2 G
    N]`` (``ssd_scan_packed``): no slice of them is copied out.  ``y`` and
    its cotangent go as ``[B, H * P, S]``, positions along the lanes, the
    layout in which the gated group norm after the scan regroups its
    channels for free (with the channels along the lanes the norm's float32
    passes each take a copy; a tile is transposed in VMEM instead).  The
    steps go in as rows of ``Q`` positions, ``[B, H, S / Q, 8, Q]``
    (``_rows``: the running sum ``c`` of ``dt * A`` inside each chunk,
    ``dt``, ``c`` at the chunk's last position, ``D``); a kernel turns a row
    into a column with one lane-dense square transpose (``_column``);
  * a program's heads are taken a 128-lane tile at a time (two heads of 64):
    the terms a tile's heads share a form for (``C S^T``, the state's update,
    ``D u``) are one product or one pass over the tile, a head's quantity in
    that head's lanes (``_Tile``); the intra-chunk product ``(L o C B^T)
    (dt o u)``, whose left side is the head's own, is one product a head
    over the whole tile with the other head's lanes thrown away, which costs
    the MXU what a product of 64 output lanes costs it;
  * matrix-product operands are in the inputs' dtype (bfloat16 in training)
    with float32 accumulation, ``L o C B^T`` rounded to it before its
    product; the decays, their running sums and the state are float32;
  * the backward kernel walks the blocks last to first with the state's
    cotangent in VMEM.  For a block it walks forward once from the saved
    state, keeping each chunk's start state in VMEM, then back through the
    chunks.  ``dB`` and ``dC`` are summed over the group's heads; the rows'
    cotangent carries ``dt``'s, ``A``'s (through ``c``) and ``D``'s, which
    XLA adds up as the derivative of ``_rows``.

Both stay inside the 16 MiB of VMEM an operation may scope by default (PERF.md,
PR 26: a kernel that asks for more takes it from its neighbours' prefetch).
Kernel playbook: /opt/skills/guides/pallas_guide.md.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 8      # rows of a step block, a float32 tile's sublanes
_NN = (((1,), (0,)), ((), ()))    # (m, k) x (k, n) -> (m, n)
_NT = (((1,), (1,)), ((), ()))    # (m, d) x (n, d) -> (m, n)
_TN = (((0,), (0,)), ((), ()))    # (k, m) x (k, n) -> (m, n)

# Chunks a grid step.  The backward kernel holds a block's inputs, its
# results (both double-buffered) and every chunk's start state: at the
# benchmark's cell 16 chunks would reckon 22 MiB, 8 reckon 12.5 (the compiler
# scopes 13.4 MiB).
SPAN = 8


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _column(row):
    """``(1, Q)`` -> ``(Q, Q)`` with entry ``[i, j] = row[i]``: broadcast
    down the sublanes and one square transpose."""
    return jnp.broadcast_to(row, (row.shape[1], row.shape[1])).T


def _widen(m, w):
    """A matrix whose lanes are all equal as ``(rows, w)``."""
    if m.shape[1] == w:
        return m
    if m.shape[1] > w:
        return m[:, :w]
    return jnp.broadcast_to(m[:, :1], (m.shape[0], w))


def _pick(which, parts):
    """``parts[e]`` where ``which == e``."""
    out = parts[-1]
    for e in reversed(range(len(parts) - 1)):
        out = jnp.where(which == e, parts[e], out)
    return out


def _lane_width(p, rep):
    """Lanes of a tile: the most of a group's ``rep`` heads of ``p``
    channels (a divisor of ``rep``) that fit 128 lanes, or one head."""
    per = max(e for e in range(1, rep + 1)
              if rep % e == 0 and e * p <= max(p, _LANES))
    return per * p


class _Tile:
    """What the heads of one ``(Q, w)`` lane tile need in a chunk before the
    state comes in, from their step blocks ``rows`` (``_rows``): each
    quantity a head's in that head's ``p`` lanes (``keep``: in that head's
    rows of the state)."""

    def __init__(self, rows, p, w, n):
        q = rows[0].shape[1]
        self.rows = rows
        self.lane = jax.lax.broadcasted_iota(jnp.int32, (q, w), 1) // p
        self.cum_cols = [_column(r[0:1]) for r in rows]          # c_i
        cum = _pick(self.lane, [_widen(x, w) for x in self.cum_cols])
        last = _pick(self.lane[:1], [_widen(r[2:3], w) for r in rows])
        self.dt = _pick(self.lane, [_widen(_column(r[1:2]), w)
                                    for r in rows])
        self.skip = _pick(self.lane[:1], [_widen(r[3:4], w) for r in rows])
        self.up = jnp.exp(cum)                      # exp(c_i)
        self.rest = jnp.exp(last - cum)             # exp(c_Q - c_i)
        state_row = jax.lax.broadcasted_iota(jnp.int32, (w, n), 0) // p
        self.keep = jnp.exp(_pick(state_row, [jnp.broadcast_to(
            _widen(r[2:3], n), (w, n)) for r in rows]))          # exp(c_Q)

    def head(self, e, g):
        """Head ``e``'s decay tile ``L`` and ``L o C B^T``, float32."""
        row = self.rows[e][0:1]
        q = row.shape[1]
        i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        # exp(c_i - c_j) where j <= i: no exponent is positive
        decay = jnp.exp(jnp.where(j <= i, self.cum_cols[e]
                                  - jnp.broadcast_to(row, (q, q)), -jnp.inf))
        return decay, decay * g


def _next_state(t, s0, x, b, dtype):
    """The state after the chunk: ``exp(c_Q) S_0 + (exp(c_Q - c) o x)^T B``
    with ``x = dt o u``, float32 ``(w, N)``."""
    return t.keep * s0 + _dot((t.rest * x).astype(dtype), b, _TN)


def _fwd_kernel(u_ref, b_ref, c_ref, rows_ref, y_ref, start_ref, s_scr, *,
                p, w):
    dtype = u_ref.dtype
    q, n = rows_ref.shape[-1], b_ref.shape[-1]
    per = w // p

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    start_ref[0, 0, 0] = s_scr[...]

    def body(j, carry):
        at = pl.ds(pl.multiple_of(j * q, q), q)
        b, c = b_ref[0, at, :], c_ref[0, at, :]
        g = _dot(c, b, _NT)                         # C B^T, once a group
        for k in range(rows_ref.shape[1] // per):
            lanes = slice(k * w, (k + 1) * w)
            t = _Tile([rows_ref[0, k * per + e, j] for e in range(per)],
                      p, w, n)
            u = u_ref[0, at, lanes].astype(jnp.float32)
            x = t.dt * u
            xm = x.astype(dtype)
            s0 = s_scr[lanes, :]
            y = t.up * _dot(c, s0.astype(dtype), _NT) + t.skip * u
            for e in range(per):
                m = t.head(e, g)[1].astype(dtype)
                y = y + jnp.where(t.lane == e, _dot(m, xm), 0.0)
            y_ref[0, lanes, at] = y.T.astype(y_ref.dtype)
            s_scr[lanes, :] = _next_state(t, s0, x, b, dtype)
        return carry

    jax.lax.fori_loop(0, u_ref.shape[1] // q, body, 0)


def _specs(rep, p, n, q, span, index, b_at=0, c_at=0):
    """Block specs of ``(u or its cotangent; B or its; C or its; the step
    rows or theirs; the block-start states)`` for a grid ``(B, G, blocks)``;
    ``index(t)`` is the block a step works on.  ``B`` and ``C`` may be
    column blocks of the same array as ``u``: ``b_at`` and ``c_at`` are the
    blocks of ``N`` columns at which their first group starts."""
    rows = span * q
    return (pl.BlockSpec((1, rows, rep * p), lambda b, g, t: (b, index(t), g)),
            pl.BlockSpec((1, rows, n),
                         lambda b, g, t: (b, index(t), b_at + g)),
            pl.BlockSpec((1, rows, n),
                         lambda b, g, t: (b, index(t), c_at + g)),
            pl.BlockSpec((1, rep, span, _ROWS, q),
                         lambda b, g, t: (b, g, index(t), 0, 0)),
            pl.BlockSpec((1, 1, 1, rep * p, n),
                         lambda b, g, t: (b, g, index(t), 0, 0)))


def _y_spec(rep, p, q, span, index):
    """Block spec of ``y`` (and its cotangent) in the ``[B, H * P, S]``
    layout: positions along the lanes, as the gated group norm after the
    scan takes it (a layout with the channels along the lanes costs the
    norm's float32 passes a copy each to regroup them)."""
    return pl.BlockSpec((1, rep * p, span * q),
                        lambda b, g, t: (b, g, index(t)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(xbc, rows, *, rep, p, n, w, q, span, interpret):
    bsz, s, _ = xbc.shape
    h = rows.shape[1]
    groups, nb = h // rep, s // (span * q)
    at = h * p // n
    u, bb, cc, row, start = _specs(rep, p, n, q, span, lambda t: t,
                                   at, at + groups)
    y = _y_spec(rep, p, q, span, lambda t: t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, p=p, w=w),
        grid=(bsz, groups, nb),
        in_specs=[u, bb, cc, row],
        out_specs=[y, start],
        out_shape=[jax.ShapeDtypeStruct((bsz, h * p, s), xbc.dtype),
                   jax.ShapeDtypeStruct((bsz, groups, nb, rep * p, n),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rep * p, n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(xbc, xbc, xbc, rows)


def _total(x):
    """The sum of a float32 matrix as ``(1, 1)``."""
    return jnp.sum(jnp.sum(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _bwd_kernel(u_ref, b_ref, c_ref, rows_ref, start_ref, dy_ref,
                du_ref, db_ref, dc_ref, drows_ref, ds_scr, st_scr, *, p, w):
    dtype = u_ref.dtype
    f32 = jnp.float32
    q, n = rows_ref.shape[-1], b_ref.shape[-1]
    per = w // p
    span = u_ref.shape[1] // q
    tiles = range(rows_ref.shape[1] // per)
    first = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == 0

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    st_scr[0] = start_ref[0, 0, 0]

    def at(j):
        return pl.ds(pl.multiple_of(j * q, q), q)

    def step_rows(k, j):
        return [rows_ref[0, k * per + e, j] for e in range(per)]

    # forward through the block: every chunk's start state
    def forward(j, carry):
        b = b_ref[0, at(j), :]
        for k in tiles:
            lanes = slice(k * w, (k + 1) * w)
            t = _Tile(step_rows(k, j), p, w, n)
            x = t.dt * u_ref[0, at(j), lanes].astype(f32)
            st_scr[j + 1, lanes, :] = _next_state(t, st_scr[j, lanes, :], x,
                                                  b, dtype)
        return carry

    jax.lax.fori_loop(0, span - 1, forward, 0)

    def backward(jj, carry):
        j = span - 1 - jj
        rows = at(j)
        b, c = b_ref[0, rows, :], c_ref[0, rows, :]
        g = _dot(c, b, _NT)
        dg = db = dc = 0.0
        for k in tiles:
            lanes = slice(k * w, (k + 1) * w)
            t = _Tile(step_rows(k, j), p, w, n)
            u = u_ref[0, rows, lanes].astype(f32)
            dy = dy_ref[0, lanes, rows].astype(f32).T
            x = t.dt * u
            xm = x.astype(dtype)
            s0, ds1 = st_scr[j, lanes, :], ds_scr[lanes, :]
            s0m, ds1m = s0.astype(dtype), ds1.astype(dtype)
            dye = (t.up * dy).astype(dtype)        # d(C S0^T)
            dw = _dot(b, ds1m, _NT)                 # d(exp(c_Q - c) x)
            dx = t.rest * dw
            zs = []
            for e in range(per):
                decay, lg = t.head(e, g)
                mine = t.lane == e
                dm = _dot(jnp.where(mine, dy, 0.0).astype(dtype), xm, _NT)
                dx = dx + jnp.where(mine, _dot(lg.astype(dtype),
                                               dy.astype(dtype), _TN), 0.0)
                dg = dg + dm * decay
                zs.append(dm * lg)                  # dL o L
            du_ref[0, rows, lanes] = (t.dt * dx + t.skip * dy).astype(
                du_ref.dtype)
            ds_scr[lanes, :] = t.keep * ds1 + _dot(dye, c, _TN)
            dc = dc + _dot(dye, s0m)
            db = db + _dot((t.rest * x).astype(dtype), ds1m)
            # the step rows: every exponent's cotangent goes to the c it
            # holds, a position's sums over its head's lanes
            back = t.rest * dw * x                  # d exp(c_Q - c) o it
            into = (t.up * dy * _dot(c, s0m, _NT) - back).T
            ddt = (dx * u).T
            kept = t.keep * ds1 * s0
            back_sum = jnp.sum(back, axis=0, keepdims=True)
            skip_sum = jnp.sum(dy * u, axis=0, keepdims=True)
            for e, z in enumerate(zs):
                head = slice(e * p, (e + 1) * p)
                mine = t.lane[:1] == e
                d_cum = (jnp.sum(z.T, axis=0, keepdims=True)
                         - jnp.sum(z, axis=0, keepdims=True)
                         + jnp.sum(into[head], axis=0, keepdims=True))
                d_last = _total(kept[head]) + _total(
                    jnp.where(mine, back_sum, 0.0))
                d_skip = _total(jnp.where(mine, skip_sum, 0.0))
                drows_ref[0, k * per + e, j] = jnp.concatenate([
                    d_cum, jnp.sum(ddt[head], axis=0, keepdims=True),
                    jnp.where(first, d_last, 0.0),
                    jnp.where(first, d_skip, 0.0),
                    jnp.zeros((_ROWS - 4, q), f32)], 0)
        dgm = dg.astype(dtype)
        dc_ref[0, rows, :] = (dc + _dot(dgm, b)).astype(dc_ref.dtype)
        db_ref[0, rows, :] = (db + _dot(dgm, c, _TN)).astype(db_ref.dtype)
        return carry

    jax.lax.fori_loop(0, span, backward, 0)


def _backward(xbc, rows, starts, dy, *, rep, p, n, w, q, span, interpret):
    bsz, s, _ = xbc.shape
    h = rows.shape[1]
    groups, nb = h // rep, s // (span * q)
    at = h * p // n

    def back(t):
        return nb - 1 - t

    u, bb, cc, row, start = _specs(rep, p, n, q, span, back, at, at + groups)
    du, db, dc = _specs(rep, p, n, q, span, back)[:3]
    bc = jax.ShapeDtypeStruct((bsz, s, groups * n), xbc.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, p=p, w=w),
        grid=(bsz, groups, nb),
        in_specs=[u, bb, cc, row, start, _y_spec(rep, p, q, span, back)],
        out_specs=[du, db, dc, row],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, h * p), xbc.dtype), bc, bc,
                   jax.ShapeDtypeStruct(rows.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rep * p, n), jnp.float32),
                        pltpu.VMEM((span, rep * p, n), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(xbc, xbc, xbc, rows, starts, dy)


@functools.lru_cache(maxsize=None)
def _make_scan(rep, p, n, q, span, interpret):
    kw = dict(rep=rep, p=p, n=n, w=_lane_width(p, rep), q=q, span=span,
              interpret=interpret)

    @jax.custom_vjp
    def scan(xbc, rows):
        return _forward(xbc, rows, **kw)[0]

    def fwd(xbc, rows):
        y, starts = _forward(xbc, rows, **kw)
        return y, (xbc, rows, starts)

    def bwd(res, dy):
        du, db, dc, drows = _backward(*res, dy, **kw)
        return jnp.concatenate([du, db, dc], -1), drows

    scan.defvjp(fwd, bwd)
    return scan


def _rows(dt, a, d, q):
    """``dt`` of ``[B, S, H]`` -> ``[B, H, S / Q, 8, Q]`` float32, a row for
    each chunk of ``Q`` positions: the running sum ``c`` of ``dt * A`` inside
    the chunk, ``dt``, ``c`` at the chunk's last position, ``D``, and four
    rows of zeros that make a block a whole float32 tile."""
    b, s, h = dt.shape
    dt = dt.astype(jnp.float32)
    cum = jnp.cumsum((dt * a.astype(jnp.float32)).reshape(b, s // q, q, h),
                     axis=2)
    last = jnp.broadcast_to(cum[:, :, -1:], cum.shape)
    rows = [cum.reshape(b, s, h), dt, last.reshape(b, s, h),
            jnp.broadcast_to(d.astype(jnp.float32), (b, s, h))]
    rows = jnp.stack(rows + [jnp.zeros_like(dt)] * (_ROWS - 4), axis=-1)
    return jnp.transpose(rows.reshape(b, s // q, q, h, _ROWS),
                         (0, 3, 1, 4, 2))


# what the backward kernel may hold in VMEM by the reckoning of ``tiles``: the
# cell's shape reckons 12.5 MiB and compiles inside the default 16 MiB with
# the compiler's own temporaries
_VMEM_BUDGET = 13 * 1024 * 1024


def _vmem_bytes(chunk, p, rep, n, itemsize):
    """The backward kernel's reckoning: a block's ``u``, ``dy``, ``du``, ``B``,
    ``C``, ``dB``, ``dC``, step rows and their cotangent (double-buffered),
    the saved state in, the state's cotangent and every chunk's start state,
    and a dozen float32 ``(Q, Q)`` tiles of work."""
    rows, lanes = SPAN * chunk, rep * p
    buffered = 2 * (3 * rows * lanes * itemsize + 4 * rows * n * itemsize
                    + 2 * rep * SPAN * _ROWS * chunk * 4 + lanes * n * 4)
    return buffered + (SPAN + 1) * lanes * n * 4 + 12 * chunk * chunk * 4


def tiles(chunk, p, rep, n, itemsize=2):
    """Whether the compiled kernels take these sizes: chunks of 128, a state
    of whole 128-lane tiles, a group's heads of whole 128-lane tiles (two
    heads of 64, one of 128 or 256), and the backward kernel's block inside
    ``_VMEM_BUDGET``."""
    return (chunk == _LANES and n % _LANES == 0 and (rep * p) % _LANES == 0
            and (_LANES % p == 0 or p % _LANES == 0)
            and _vmem_bytes(chunk, p, rep, n, itemsize) <= _VMEM_BUDGET)


def ssd_scan_packed(xbc, dt, a, d, groups, state, chunk=128, dtype=None,
                    interpret=False):
    """The scan of ``ops/ssd.py:ssd_chunked`` through both kernels, over its
    inputs as the mixer holds them: ``xbc`` of ``[B, S, H * P + 2 * G * N]``
    is ``u``, ``B`` and ``C`` side by side (the convolved channels), ``dt``
    ``[B, S, H]``; the kernels read the three as column blocks of it, and the
    cotangent comes back in the same layout.  The sequence is padded to whole
    blocks with positions of step 0, which neither decay the state nor write
    to it.  Returns ``y`` of ``[B, S, H, P]`` in ``dtype``.  ``interpret``
    runs the kernels in the Pallas interpreter (the tests' way to them on a
    CPU, at any size)."""
    dtype = jnp.dtype(dtype or xbc.dtype)
    bsz, s, width = xbc.shape
    h, g, n = dt.shape[2], groups, state
    rep, inner = h // g, width - 2 * g * n
    p = inner // h
    if rep * g != h:
        raise ValueError(f"{h} heads over {g} groups")
    if p * h != inner or inner % n:
        raise ValueError(f"{inner} channels of {h} heads before B and C of "
                         f"{n}")
    sp = min(SPAN, -(-s // chunk))      # chunks a grid step
    s_pad = -(-s // (sp * chunk)) * sp * chunk

    def pad(x):
        return jnp.pad(x, [(0, 0), (0, s_pad - s)] + [(0, 0)] * (x.ndim - 2))

    y = _make_scan(rep, p, n, chunk, sp, bool(interpret))(
        pad(xbc).astype(dtype), _rows(pad(dt), a, d, chunk))
    return jnp.swapaxes(y, 1, 2).reshape(bsz, s_pad, h, p)[:, :s]


def ssd_scan(u, dt, a, b, c, d, chunk=128, dtype=None, interpret=False):
    """``ops/ssd.py:ssd_chunked`` through the kernels: the same arguments and
    result (``u``, ``B`` and ``C`` laid side by side for
    ``ssd_scan_packed``)."""
    dtype = jnp.dtype(dtype or u.dtype)
    bsz, s = u.shape[:2]
    xbc = jnp.concatenate([x.astype(dtype).reshape(bsz, s, -1)
                           for x in (u, b, c)], -1)
    return ssd_scan_packed(xbc, dt, a, d, b.shape[2], b.shape[3], chunk,
                           dtype, interpret)
