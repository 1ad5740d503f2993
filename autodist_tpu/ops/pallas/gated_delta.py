"""The chunked gated delta rule (``ops/gated_delta.py`` has the equations) as
two Pallas TPU kernels joined by a ``jax.custom_vjp``.

What the scan form pays for and these do not: every intermediate of a chunk
(the decay matrix, ``K K^T``, the triangular inverse, ``U``) is a tensor in
HBM there, the float32 state of every head goes through HBM at every chunk,
and the forward runs once more inside the block checkpoint.  Here a program
owns one key head of one sequence with the value heads it serves and walks
that sequence's chunks in order (the last grid axis, sequential); a chunk's
operands and the ``[d_k, d_v]`` float32 state of each value head stay in
VMEM from the first chunk to the last.  A grid step is a block of
``CHUNKS_PER_BLOCK`` chunks; only ``o`` and the state at each block's start
leave the forward kernel, and those states with the inputs are all the
backward pass keeps.

  * q, k, v and o are read and written as 128-lane column blocks of the
    ``[B, S, H * d]`` view the model holds: no transpose and no repeat is
    made round the kernels, a value head finds its key head in the block
    index map.  The gates go in as rows of ``W = 2c`` positions,
    ``[B, H_v, S / W, 8, W]`` (``_gate_rows``); a kernel turns a row into a
    column with one lane-dense square transpose;
  * what does not need the state is computed for ``W`` = 128 positions at
    once, two chunks of 64 as the diagonal blocks of ``W x W`` matrices, every
    operand a whole ``(128, 128)`` tile: the decay matrix, ``Q K^T``, and the
    unit triangular inverse ``T`` as the product of powers ``(I + x)(I +
    x^2)(I + x^4)...``, float32, each product three bfloat16 terms in two
    passes of the MXU (``_times``: what ``Precision.HIGH`` is, 2**-16 a
    product).  The chunks then take their turn with the state: ``U = T (beta
    (V - exp(G) K S))`` (the same ``U_0 - W S`` with one product less),
    ``O``, the new state;
  * matrix-product operands are in the inputs' dtype (bfloat16 in training)
    with float32 accumulation; decays, the inverse and the state are float32;
  * the backward kernel walks the blocks last to first with the state's
    cotangent in VMEM.  For a block it walks forward once from the saved
    state, keeping each chunk's start state and each inverse in VMEM, then
    back through the chunks.  ``dq`` and ``dk`` are summed over the value
    heads of the key head in the program;
  * the value heads of a program go step by step together through every
    chain of dependent products (the inverse's six, the chunks' walk): the
    scheduler keeps the program's order on an MXU, so chains written one
    after the other run one after the other (PERF.md section 5: the LLO
    bundle dumps of the deviceless compile).

Both stay inside the 16 MiB of VMEM an operation may scope by default (PERF.md,
PR 26: a kernel that asks for more takes it from its neighbours' prefetch).
Kernel playbook: /opt/skills/guides/pallas_guide.md.
"""
import functools
import math
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
_ROWS = 8      # rows of a gate block, a float32 tile's sublanes
_NN = (((1,), (0,)), ((), ()))    # (m, k) x (k, n) -> (m, n)
_NT = (((1,), (1,)), ((), ()))    # (m, d) x (n, d) -> (m, n)
_TN = (((0,), (0,)), ((), ()))    # (k, m) x (k, n) -> (m, n)


def _dot(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _split(x):
    """float32 ``x`` as two bfloat16 terms, ``hi + lo = x`` to 2**-16."""
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _halves(shape, c):
    """``(row, column)`` of each entry of a ``(2c, 2c)`` matrix inside its
    ``c x c`` quarter."""
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jnp.where(i < c, i, i - c), jnp.where(j < c, j, j - c)


def _times(lefts, y, c, left):
    """``x y`` for each ``x`` of ``lefts`` and for ``y``, float32 ``c x c``
    matrices two at a time in the twice-written form of ``_inverse``; each
    float32 product is three bfloat16 terms ``hi hi + lo hi + hi lo`` (what
    ``jax.lax.Precision.HIGH`` is and Mosaic does not offer; the ``lo lo``
    term is 2**-16 of the product) in two passes of the MXU: a chunk's rows
    ``[hi | lo]`` against ``[[hi, hi], [hi, hi]]`` and against
    ``[[lo, lo], [0, 0]]`` of ``y``.  The ``lefts`` share the weights, and no
    lane moves."""
    hi, lo = _split(y)
    rows = []
    for p in range(2):
        part = slice(p * c, (p + 1) * c)
        pairs = jnp.concatenate([jnp.where(left, *_split(x[part]))
                                 for x in lefts], 0)
        rows.append(
            _dot(pairs, jnp.concatenate([hi[part], hi[part]], 0))
            + _dot(pairs, jnp.concatenate([lo[part],
                                           jnp.zeros_like(lo[part])], 0)))
    return [jnp.concatenate([rows[p][n * c:(n + 1) * c] for p in range(2)], 0)
            for n in range(len(lefts))]


def _inverse(many, c):
    """``(I + a)^-1`` for each ``a`` of ``many``, two strictly lower
    triangular float32 ``c x c`` matrices each written twice along the lanes:
    ``a`` is ``[[a_0, a_0], [a_1, a_1]]`` and so is the result.  With ``x =
    -a`` nilpotent the inverse is the finite product ``(I + x)(I + x^2)(I +
    x^4)...``, as ``ops/gated_delta.py:inv_unit_lower`` has it.  (Written
    twice, a matrix's ``hi`` and ``lo`` terms sit side by side for ``_times``
    without a rotation of lanes, which costs the XLU more than the MXU
    saves.)  The ``many`` go turn by turn together: each inverse is a chain
    of dependent products, and the scheduler keeps the program's order on an
    MXU, so chains written one after the other run one after the other."""
    i, j = _halves(many[0].shape, c)
    left = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1) < c
    xs = [-a for a in many]
    ts = [jnp.where(i == j, 1.0, x) for x in xs]
    turns = max(0, math.ceil(math.log2(max(c, 2))) - 1)
    if turns:
        xs = [_times([x], x, c, left)[0] for x in xs]
    for turn in range(turns):
        # t (I + x) and, for the next turn, x x: one set of weights
        last = turn + 1 == turns
        both = [_times([t] if last else [t, x], x, c, left)
                for t, x in zip(ts, xs)]
        ts = [t + b[0] for t, b in zip(ts, both)]
        xs = xs if last else [b[1] for b in both]
    return ts


def _lanes(m, d):
    """A ``(W, W)`` matrix whose lanes are all equal as ``(W, d)``."""
    if m.shape[1] == d:
        return m
    return jnp.broadcast_to(m[:, :1], (m.shape[0], d))


def _column(row):
    """``(1, W)`` -> ``(W, W)`` with entry ``[i, j] = row[i]``: broadcast
    down the sublanes and one square transpose (a reshape would move the
    row element by element)."""
    return jnp.broadcast_to(row, (row.shape[1], row.shape[1])).T


class _Chunks:
    """What ``W`` positions of one value head share before the state comes
    in.  ``rows`` is the gate block (``_gate_rows``), of which this reads
    the running sum ``G`` of ``g`` inside each chunk, ``beta``, and ``G`` at
    the chunk's last position."""

    def __init__(self, rows, c):
        w = rows.shape[1]
        self.c, self.w, self.rows = c, w, rows
        self.g_row = jnp.broadcast_to(rows[0:1], (w, w))      # G_j
        self.g_col = _column(rows[0:1])                       # G_i
        self.beta = _column(rows[1:2])
        self.last = _column(rows[2:3])
        i = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
        # the two chunks of the W rows are diagonal blocks
        same = (i < c) == (j < c)
        self.low, self.strict = same & (j <= i), same & (j < i)
        # exp(G_i - G_j) where j <= i in one chunk: no exponent is positive
        self.decay = jnp.exp(jnp.where(self.low, self.g_col - self.g_row,
                                       -jnp.inf))
        self.up = jnp.exp(self.g_col)                         # exp(G_i)
        self.rest = jnp.exp(self.last - self.g_col)           # exp(G_C - G_i)

    def chunk(self, x, i):
        """Rows of chunk ``i`` of a ``(W, ...)`` array."""
        return x[i * self.c:(i + 1) * self.c]

    def widen(self, x, i):
        """Chunk ``i``'s ``(c, d)`` rows as ``(W, d)``, zero elsewhere: the
        right-hand side for a product with chunk ``i``'s rows of a ``(W,
        W)`` matrix that holds the chunks' blocks on its diagonal, or twice
        side by side, without a lane slice."""
        zero = jnp.zeros_like(x)
        return jnp.concatenate([zero, x] if i else [x, zero], 0)

    def keep(self, state, i):
        """``exp(G_C)`` of chunk ``i`` times the state."""
        row = jnp.exp(self.last[i * self.c:i * self.c + 1])   # (1, W)
        return _lanes(jnp.broadcast_to(row, (state.shape[0], self.w)),
                      state.shape[1]) * state


def _inverses(k, heads, c):
    """``T = (I + A)^-1``, ``A = strict(beta_i exp(G_i - G_j) k_i . k_j)``, of
    the two chunks of the ``W`` rows ``k`` for each value head's ``_Chunks``
    of ``heads``, float32 in the twice-written form ``[[T_0, T_0], [T_1,
    T_1]]``: chunk ``i``'s rows times a right-hand side that is zero outside
    chunk ``i``'s rows (``_Chunks.widen``) is ``T_i`` times it, as for a
    block-diagonal ``T``.  Rows 3 and 4 of a gate block are the two chunks'
    ``G`` written twice."""
    w = 2 * c
    i, j = _halves((w, w), c)
    top = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0) < c
    kk = jnp.concatenate([
        _dot(k[p * c:(p + 1) * c],
             jnp.concatenate([k[p * c:(p + 1) * c]] * 2, 0), _NT)
        for p in range(2)], 0)
    many = []
    for ch in heads:
        g_row = jnp.where(top, jnp.broadcast_to(ch.rows[3:4], (w, w)),
                          jnp.broadcast_to(ch.rows[4:5], (w, w)))
        # exp(G_i - G_j) for j < i only: no exponent is positive
        decay = jnp.exp(jnp.where(j < i, ch.g_col - g_row, -jnp.inf))
        many.append(ch.beta * decay * kk)
    return _inverse(many, c)


def _chunk_forward(ch, i, q, k, v, t, state, dtype):
    """Chunk ``i`` of the ``W`` rows with the state it starts from:
    ``(Q S, K S, V - exp(G) K S, U)``, all float32 ``(c, d_v)``."""
    d_v = v.shape[1]
    qk_s = _dot(jnp.concatenate([ch.chunk(q, i), ch.chunk(k, i)], 0),
                state.astype(dtype))
    q_s, k_s = qk_s[:ch.c], qk_s[ch.c:]
    inner = ch.chunk(v, i).astype(jnp.float32) \
        - ch.chunk(_lanes(ch.up, d_v), i) * k_s
    r = ch.chunk(_lanes(ch.beta, d_v), i) * inner
    u = _dot(ch.chunk(t, i), ch.widen(r.astype(dtype), i))
    return q_s, k_s, inner, u


def _next_state(ch, i, k, u, state, dtype):
    d_v = u.shape[1]
    u_rest = (ch.chunk(_lanes(ch.rest, d_v), i) * u).astype(dtype)
    return ch.keep(state, i) + _dot(ch.chunk(k, i), u_rest, _TN)


def _fwd_kernel(q_ref, k_ref, v_ref, gate_ref, o_ref, start_ref, s_scr, *,
                rep, c, w, d_v):
    dtype = q_ref.dtype
    heads = range(rep)

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)

    for r in heads:
        start_ref[0, r, 0] = s_scr[r]

    def body(j, carry):
        rows = pl.ds(pl.multiple_of(j * w, w), w)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        chs = [_Chunks(gate_ref[0, r, j], c) for r in heads]
        ts = [t.astype(dtype) for t in _inverses(k, chs, c)]
        qk = _dot(q, k, _NT)
        vs = [v_ref[0, rows, r * d_v:(r + 1) * d_v] for r in heads]
        ps = [(qk * ch.decay).astype(dtype) for ch in chs]
        out = [[] for _ in heads]
        for i in range(2):       # the chunks in order, the heads together
            for r, ch in enumerate(chs):
                state = s_scr[r]
                q_s, _, _, u = _chunk_forward(ch, i, q, k, vs[r], ts[r],
                                              state, dtype)
                out[r].append(ch.chunk(_lanes(ch.up, d_v), i) * q_s
                              + _dot(ch.chunk(ps[r], i),
                                     ch.widen(u.astype(dtype), i)))
                s_scr[r] = _next_state(ch, i, k, u, state, dtype)
        for r in heads:
            o_ref[0, rows, r * d_v:(r + 1) * d_v] = jnp.concatenate(
                out[r], 0).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[1] // w, body, 0)


def _specs(rep, span, c, w, d_k, d_v, index):
    """Block specs of ``(q or k, v or o, gates, block-start states)`` for a
    grid ``(B, H_k, blocks)``; ``index(t)`` is the block a step works on."""
    rows = span * c
    return (pl.BlockSpec((1, rows, d_k), lambda b, h, t: (b, index(t), h)),
            pl.BlockSpec((1, rows, rep * d_v),
                         lambda b, h, t: (b, index(t), h)),
            pl.BlockSpec((1, rep, rows // w, _ROWS, w),
                         lambda b, h, t: (b, h, index(t), 0, 0)),
            pl.BlockSpec((1, rep, 1, d_k, d_v),
                         lambda b, h, t: (b, h, index(t), 0, 0)))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _forward(q, k, v, gates, *, rep, c, span, interpret):
    b, s, n_k = q.shape
    h_v, w = gates.shape[1], gates.shape[-1]
    h_k = h_v // rep
    d_k, d_v = n_k // h_k, v.shape[2] // h_v
    nb = s // (span * c)
    qk, vo, gate, start = _specs(rep, span, c, w, d_k, d_v, lambda t: t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rep=rep, c=c, w=w, d_v=d_v),
        grid=(b, h_k, nb),
        in_specs=[qk, qk, vo, gate],
        out_specs=[vo, start],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, h_v, nb, d_k, d_v), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rep, d_k, d_v), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v, gates)


def _row_of_sums(*terms):
    """The row sums of the ``(W, d)`` ``terms`` added up, as a ``(1, W)``
    row: a square transpose and a sum down the sublanes, where a sum along
    the lanes would leave a column to be transposed after it."""
    by_shape = {}
    for t in terms:
        by_shape[t.shape] = by_shape[t.shape] + t if t.shape in by_shape \
            else t
    return sum(jnp.sum(t.T, axis=0, keepdims=True)
               for t in by_shape.values())


def _bwd_kernel(q_ref, k_ref, v_ref, gate_ref, start_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dgate_ref,
                ds_scr, st_scr, t_scr, tt_scr, *, rep, c, w, d_v):
    dtype = q_ref.dtype
    f32 = jnp.float32
    n = q_ref.shape[1] // w
    heads = range(rep)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    for r in heads:
        st_scr[r, 0] = start_ref[0, r, 0]

    def at(j):
        return pl.ds(pl.multiple_of(j * w, w), w)

    # forward through the block: every chunk's start state, every W rows'
    # inverse and its transpose
    def forward(j, carry):
        rows = at(j)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        chs = [_Chunks(gate_ref[0, r, j], c) for r in heads]
        ts = _inverses(k, chs, c)
        for r in heads:
            t_scr[r, j] = ts[r].astype(dtype)
            tt_scr[r, j] = ts[r].T.astype(dtype)
        vs = [v_ref[0, rows, r * d_v:(r + 1) * d_v] for r in heads]
        for i in range(2):
            for r, ch in enumerate(chs):
                state = st_scr[r, 2 * j + i]
                u = _chunk_forward(ch, i, q, k, vs[r], ts[r].astype(dtype),
                                   state, dtype)[3]
                st_scr[r, 2 * j + i + 1] = _next_state(ch, i, k, u, state,
                                                       dtype)
        return carry

    jax.lax.fori_loop(0, n, forward, 0)

    def backward(jj, carry):
        j = n - 1 - jj
        rows = at(j)
        q, k = q_ref[0, rows, :], k_ref[0, rows, :]
        kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        # what needs no cotangent of the state, a head after the other
        hs = []
        for r in heads:
            h = types.SimpleNamespace(ch=_Chunks(gate_ref[0, r, j], c))
            ch = h.ch
            h.up, h.rest, h.beta = (_lanes(x, d_v)
                                    for x in (ch.up, ch.rest, ch.beta))
            h.tt = tt_scr[r, j]
            h.p = qk * ch.decay
            h.v = v_ref[0, rows, r * d_v:(r + 1) * d_v]
            h.do = do_ref[0, rows, r * d_v:(r + 1) * d_v]
            h.starts = [st_scr[r, 2 * j + i] for i in range(2)]
            again = [_chunk_forward(ch, i, q, k, h.v, t_scr[r, j],
                                    h.starts[i], dtype) for i in range(2)]
            h.q_s, h.k_s, h.inner, h.u = (jnp.concatenate(x, 0)
                                          for x in zip(*again))
            h.do32 = h.do.astype(f32)
            h.pt_do = _dot(h.p.T.astype(dtype), h.do)    # dU from O = P U
            h.parts, h.d_last = [], 0.0
            hs.append(h)
        # the chunks, last first, with the state's cotangent; heads together
        for i in reversed(range(2)):
            qk_i = jnp.concatenate([hs[0].ch.chunk(q, i),
                                    hs[0].ch.chunk(k, i)], 0)
            for r, h in enumerate(hs):
                ch = h.ch
                ds1 = ds_scr[r]
                ds1_m = ds1.astype(dtype)
                k_ds = _dot(ch.chunk(k, i), ds1_m)       # d(rest * U)
                du = ch.chunk(h.pt_do, i) + ch.chunk(h.rest, i) * k_ds
                dr = _dot(ch.chunk(h.tt, i), ch.widen(du.astype(dtype), i))
                dinner = ch.chunk(h.beta, i) * dr
                both = jnp.concatenate(
                    [ch.chunk(h.up * h.do32, i), -ch.chunk(h.up, i) * dinner],
                    0).astype(dtype)                     # d(Q S), d(K S)
                ds_scr[r] = ch.keep(ds1, i) + _dot(qk_i, both, _TN)
                dqk_i = _dot(both, h.starts[i].astype(dtype), _NT)
                u_rest = (ch.chunk(h.rest, i)
                          * ch.chunk(h.u, i)).astype(dtype)
                dk_i = dqk_i[c:] + _dot(u_rest, ds1_m, _NT)
                h.parts.append((dqk_i[:c], dk_i, k_ds, dr, dinner))
                # exp(G_C) S: its cotangent, on the chunk's first position
                keep = jnp.sum(jnp.sum(ds1 * h.starts[i], axis=0,
                                       keepdims=True), axis=1, keepdims=True)
                h.d_last = h.d_last + jnp.where(
                    lane == i * c,
                    keep * jnp.exp(ch.last[i * c:i * c + 1]), 0.0)
        # what the chunks leave: A, P and the gates
        dq = dk = 0.0
        for r, h in enumerate(hs):
            ch = h.ch
            dq_s, dk_s, k_ds, dr, dinner = (
                jnp.concatenate(x[::-1], 0) for x in zip(*h.parts))
            u_m = h.u.astype(dtype)
            # A = strict(beta decay K K^T) and T = (I + A)^-1, U = T R:
            # dA = -T^T dT T^T with dT = dU R^T, that is -dR U^T
            da = jnp.where(ch.strict, -_dot(dr.astype(dtype), u_m, _NT), 0.0)
            dp = jnp.where(ch.low, _dot(h.do, u_m, _NT), 0.0)
            dkk = (da * ch.beta * ch.decay).astype(dtype)
            dqk = (dp * ch.decay).astype(dtype)
            dq = dq + dq_s + _dot(dqk, k)
            dk = dk + dk_s + _dot(dkk, k) + _dot(dkk, k, _TN) \
                + _dot(dqk, q, _TN)
            dv_ref[0, rows, r * d_v:(r + 1) * d_v] = dinner.astype(
                dv_ref.dtype)
            # the gates: every exponent's cotangent goes to the G it holds
            m = (da * ch.beta * kk + dp * qk) * ch.decay   # dD * D
            d_rest = h.rest * k_ds * h.u                   # de * e
            dgate_ref[0, r, j] = jnp.concatenate([
                _row_of_sums(m, h.up * (h.do32 * h.q_s - dinner * h.k_s),
                             -d_rest) - jnp.sum(m, axis=0, keepdims=True),
                _row_of_sums(dr * h.inner, da * ch.decay * kk),
                _row_of_sums(d_rest) + h.d_last,
                jnp.zeros((_ROWS - 3, w), f32)], 0)
        dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        return carry

    jax.lax.fori_loop(0, n, backward, 0)


def _backward(q, k, v, gates, starts, do, *, rep, c, span, interpret):
    b, s, n_k = q.shape
    h_v, w = gates.shape[1], gates.shape[-1]
    h_k = h_v // rep
    d_k, d_v = n_k // h_k, v.shape[2] // h_v
    nb = s // (span * c)
    qk, vo, gate, start = _specs(rep, span, c, w, d_k, d_v,
                                 lambda t: nb - 1 - t)
    n = span * c // w
    return pl.pallas_call(
        functools.partial(_bwd_kernel, rep=rep, c=c, w=w, d_v=d_v),
        grid=(b, h_k, nb),
        in_specs=[qk, qk, vo, gate, start, vo],
        out_specs=[qk, qk, vo, gate],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(gates.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rep, d_k, d_v), jnp.float32),
                        pltpu.VMEM((rep, span + 1, d_k, d_v), jnp.float32),
                        pltpu.VMEM((rep, n, w, w), q.dtype),
                        pltpu.VMEM((rep, n, w, w), q.dtype)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(q, k, v, gates, starts, do)


@functools.lru_cache(maxsize=None)
def _make_rule(rep, c, span, interpret):
    kw = dict(rep=rep, c=c, span=span, interpret=interpret)

    @jax.custom_vjp
    def rule(q, k, v, gates):
        return _forward(q, k, v, gates, **kw)[0]

    def fwd(q, k, v, gates):
        o, starts = _forward(q, k, v, gates, **kw)
        return o, (q, k, v, gates, starts)

    def bwd(res, do):
        return tuple(_backward(*res, do, **kw))

    rule.defvjp(fwd, bwd)
    return rule


def _gate_rows(g, beta, c, w):
    """``g``, ``beta`` of ``[B, S, H_v]`` -> ``[B, H_v, S / W, 8, W]``
    float32, a row for ``W = 2c`` positions each: the running sum ``G`` of
    ``g`` inside each chunk, ``beta``, ``G`` at the chunk's last position,
    the first and the second chunk's ``G`` written twice (what the inverse's
    form wants, ``_inverses``), and three rows of zeros that make a block a
    whole float32 tile."""
    b, s, h = g.shape
    cum = jnp.cumsum(g.astype(jnp.float32).reshape(b, s // c, c, h), axis=2)
    last = jnp.broadcast_to(cum[:, :, -1:], cum.shape)
    pairs = cum.reshape(b, s // w, 2, c, h)
    twice = [jnp.concatenate([pairs[:, :, p]] * 2, 2).reshape(b, s, h)
             for p in range(2)]
    rows = [cum.reshape(b, s, h), beta.astype(jnp.float32),
            last.reshape(b, s, h)] + twice
    rows = jnp.stack(rows + [jnp.zeros_like(rows[0])] * (_ROWS - 5), axis=-1)
    return jnp.transpose(rows.reshape(b, s // w, w, h, _ROWS),
                         (0, 3, 1, 4, 2))


# what the backward kernel may hold in VMEM by the reckoning of ``tiles``: the
# cell's shape reckons 8.4 MiB and compiles inside the default 16 MiB with
# the compiler's own temporaries
_VMEM_BUDGET = 10 * 1024 * 1024


def tiles(c, d_k, d_v, rep=1, itemsize=2):
    """Whether the compiled kernels take these sizes: heads whose width is a
    whole number of 128-lane column blocks, two chunks a 128 x 128 tile, and
    the backward kernel's block (every chunk's start state of ``rep`` value
    heads, their inverses, the double-buffered operands and results) inside
    ``_VMEM_BUDGET``."""
    from autodist_tpu.ops.gated_delta import CHUNKS_PER_BLOCK as span

    rows = span * c
    held = rep * ((span + 3) * d_k * d_v * 4             # states, f32
                  + 2 * (rows // _LANES) * _LANES * _LANES * itemsize
                  + 2 * 3 * rows * d_v * itemsize) \
        + 2 * 4 * rows * d_k * itemsize
    return d_k % _LANES == 0 and d_v % _LANES == 0 and 2 * c == _LANES \
        and held <= _VMEM_BUDGET


def gated_delta_rule(q, k, v, g, beta, chunk_size=64, dtype=None,
                     interpret=False):
    """``ops/gated_delta.py:chunk_gated_delta_rule`` through the kernels:
    the same arguments and result.  ``interpret`` runs them in the Pallas
    interpreter (the tests' way to them on a CPU, at any size)."""
    from autodist_tpu.ops.gated_delta import CHUNKS_PER_BLOCK

    dtype = jnp.dtype(dtype or v.dtype)
    b, s, h_v, d_v = v.shape
    h_k = q.shape[2]
    rep = h_v // h_k
    if rep * h_k != h_v:
        raise ValueError(f"{h_v} value heads over {h_k} key heads")
    c = chunk_size
    w = 2 * c
    chunks = -(-s // w) * 2
    span = min(CHUNKS_PER_BLOCK, chunks)
    s_pad = -(-chunks // span) * span * c

    def rows(x):
        """``[B, S, ...]`` padded to whole blocks with positions that write
        nothing (``beta = 0``) and do not decay (``g = 0``)."""
        return jnp.pad(x, [(0, 0), (0, s_pad - s)] + [(0, 0)] * (x.ndim - 2))

    def flat(x):
        return rows(x).astype(dtype).reshape(b, s_pad, -1)

    o = _make_rule(rep, c, span, bool(interpret))(
        flat(q), flat(k), flat(v), _gate_rows(rows(g), rows(beta), c, w))
    return o.reshape(b, s_pad, h_v, d_v)[:, :s]
