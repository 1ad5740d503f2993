"""Pallas TPU flash attention (tiled online-softmax) with a custom VJP.

The XLA attention path (``jax.nn.dot_product_attention``) materializes the
(S, S) score matrix in HBM — O(S^2) memory traffic that caps context
length and starves the MXU at long S.  This kernel is the standard
flash-attention recipe laid out for the TPU memory hierarchy:

  * the kernels read q, k, v and dO and write out, dq, dk and dv in the
    layout the projections write and read, ``(B, S, H*D)``: a head is ``D``
    lanes of a row, nothing is transposed or padded on the way in or out,
    and ``flash_attention_packed`` takes the ``qkv`` projection's output as
    it is (the same array three times, at three lane offsets).  One program
    takes G heads as ``G*D`` contiguous lanes and, for them, one tile of q
    rows and the WHOLE K/V row in VMEM (the row is fetched once per G
    heads: its block index does not change across the q tiles).  The grid
    is (B, H / G, q tiles); the program walks its heads in a loop, one
    head's working set at a time.  A masked tile is neither fetched nor
    stepped over.  Without lane padding a program's blocks are half the
    bytes the folded layout's were at D = 64, in HBM and in VMEM
    (``_vmem_bytes``): 4 heads a program stay under the 14 MiB budget at
    the benchmark's GPT shape, and no limit is passed to Mosaic;
  * how a head is found in its lanes follows from ``D`` (``_Heads``).
    ``D`` a multiple of 128: a head is whole lane blocks, cut out by an
    aligned slice; under grouped K/V heads the program's K/V block is the
    one head its query heads share.  ``D`` dividing 128 (64, 32,
    16): ``128 / D`` heads share a lane block, which is loaded and stored
    whole; of the two operands of every product over lanes one has the
    other heads' lanes selected to zero (exact zeros: the product contracts
    over 128 lanes of which ``D`` count, at the MXU passes a contraction
    over ``D`` takes), a product into lanes comes out right in the head's
    own lanes and is stored by a lane select over what the block holds,
    the whole block at a time.  Every other case keeps the FOLDED layout,
    ``(B*H, S, D)`` made by a transpose in XLA, a head a leading index,
    padded to 128 lanes in HBM and VMEM: a ``D`` that neither divides nor
    is a multiple of 128 (80, 96), grouped K/V heads at ``D`` under 128, a
    head count that ``128 / D`` does not divide, and ring attention's step
    (``flash_block_update``), whose callers fold once for a whole ring;
  * at moderate S (the training shapes) a head's visible prefix of K is ONE
    slab whose length is static per q tile (a ``pl.when`` chain over the
    tile index): plain softmax over (block_q, prefix) scores, no running
    state, no rescaling, one long basic block for the scheduler.  Long rows,
    and ring attention's traced offsets, take the loop form instead: k
    tiles in ``fori_loop``s whose trip counts are the causal bounds, softmax
    partials merged into VMEM scratch;
  * every matmul feeds the MXU operands in the dtype of the inputs (bf16 in
    training: the probabilities and score gradients are cast to it, as
    XLA's attention does) and accumulates in f32
    (``preferred_element_type``); max, denominator, logsumexp, ``exp`` and
    all accumulators are f32.  A power-of-two softmax scale (D = 16, 64,
    256) is folded into q, where it is exact;
  * the causal ``iota``/``where`` touches only the block the diagonal
    crosses (the tail of a prefix; in the loop form a second loop after the
    unmasked one); key padding masks (the BERT case) ride a per-key
    additive bias row, and the kernels are built without that operand when
    there is no mask;
  * backward = two kernels that recompute p from the saved logsumexp
    instead of stashing the (S, S) probability matrix — the
    flash-attention memory contract.  dq holds a q tile against the prefix
    of K; dk/dv holds a k tile against the suffix of Q, on TRANSPOSED
    scores (k rows, q columns), so p^T @ dO and ds^T @ q are plain matmuls
    and the row statistics broadcast along sublanes as they are stored
    ((B*H, 1, S) f32, in every layout).  Where a kernel needs them as
    columns it goes through a lane-dense square transpose
    (``_to_row``/``_to_dense``): a reshape relayouts element by element.
    ``rowsum(dO * O)`` is taken inside both kernels from the tiles of dO
    and ``out`` they hold (``_delta``): made by XLA it costs a relayout of
    a float32 (B, S, H*D);
  * ``G`` and the tiles follow from the shapes, the dtype and a VMEM budget
    (``_pick_heads``, ``_prefix``), by the divisor rule of ``_pick_block``;
    the layout and the tiling chosen are logged once per shape.

What bounds it on a v5e at D = 64 (LLO dumps of the deviceless compile,
PERF.md section 5): every matmul half-fills the MXU (contraction or output
width 64 of 128, whichever layout), the two backward kernels are MXU-bound
at that, and the forward is bound by the f32 softmax on the VPU.

Reference parity note: the reference (petuum/autodist) has no attention
kernels at all (its models ride stock TF layers); this is part of the
"exceeds" long-context surface (SURVEY.md section 5) next to
``parallel/ring_attention.py``, which streams K/V blocks *between* chips
while this kernel tiles *within* a chip.

Kernel playbook: /opt/skills/guides/pallas_guide.md (grid/BlockSpec,
``pl.ds`` on refs, in-kernel ``fori_loop`` and ``pl.when``, MXU
preferred_element_type, 2D iota, scalar prefetch).
"""
import functools
import math
import typing

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from autodist_tpu.utils import logging

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30  # finite: -inf NaNs under (0 * -inf) in masked-row algebra
# running-max floor: keeps exp(masked - m) == 0 when a whole block (or row)
# is masked out, so fully-padded rows produce exact zeros fwd AND bwd
_M_FLOOR = -1e20
_LANES = 128
_MAX_HEADS = 8    # heads a program takes at most: past that a step's fixed
                  # cost is under a per cent of its work
# VMEM: a program of several heads stays inside the 16 MiB every operation may
# scope by default, because XLA keeps the rest (of a v5e's 128 MiB) for
# prefetching the neighbours' operands and a kernel that asks for more takes
# that away from them (PERF.md, PR 26: 2.2 ms a layer on the MLP's matmul).
# Only where one head alone passes the budget is Mosaic told a higher limit.
_VMEM_BUDGET = 14 * 1024 * 1024
_VMEM_LIMIT = 96 * 1024 * 1024
# the prefix form (below): at most this many tile indices, each with its own
# static slab length, and this much for a slab's f32 intermediates
_MAX_CASES = 8
_SLAB_BUDGET = 16 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))    # (m, d) x (n, d) -> (m, n)
_NN = (((1,), (0,)), ((), ()))    # (m, k) x (k, n) -> (m, n)


def _pick_block(s, want, multiple=1):
    """Largest divisor of ``s`` that is <= want (and a multiple of
    ``multiple``); 0 when no such divisor exists."""
    b = min(want, s)
    b -= b % multiple
    while b >= multiple and s % b:
        b -= multiple
    return b if b >= multiple else 0


class _Heads(typing.NamedTuple):
    """How a program's blocks hold its heads.

    ``pack >= 1``: the projections' layout.  A block is ``(1, rows, G*D)``
    and head ``h`` its ``D`` lanes from ``h * D``; blocks are read and
    written in lane blocks of ``pack`` heads (``pack * D`` lanes: one head
    where ``D`` is a multiple of 128, the ``128 / D`` heads of a 128-lane
    block where ``D`` divides it).  ``pack == 0``: the folded layout, a block
    is ``(G, rows, D)`` and head ``h`` its leading index."""
    d: int
    pack: int

    @property
    def width(self):
        """Lanes of what a head's ``load`` returns and ``store`` takes."""
        return self.d * max(self.pack, 1)

    @property
    def tile_axis(self):
        """The grid axis of the row tiles: after (B, head groups), or after
        the folded (head groups)."""
        return 2 if self.pack else 1

    def count(self, ref):
        """Heads of a block."""
        return ref.shape[2] // self.d if self.pack else ref.shape[0]


class _Head:
    """Head ``index`` (which may be traced: the heads of a program are a
    ``fori_loop``) of a program's blocks, whose tiles have ``rows`` rows
    (0: the head is only loaded from).  What every use of the head shares (where its lane block lies, which
    lanes of a tile are the head's) is worked out once and in plain ``lax``
    operations: a step traces these kernels 96 times, and a ``jnp``
    operation is a traced call of its own."""

    def __init__(self, heads, index, rows=0):
        self.heads, self.index, self._tile = heads, index, None
        if heads.pack > 1:
            if rows:
                self._tile = self._own((rows, heads.width))
            index = jax.lax.div(index, heads.pack)
        if heads.pack:
            self._lanes = _at(index * heads.width, heads.width, heads.width)

    def _at(self, rows):
        if not self.heads.pack:
            return (self.index, rows, slice(None))
        return (0, rows, self._lanes)

    def load(self, ref, rows=slice(None)):
        """``rows`` of the head: its own lanes, or the whole lane block it
        shares (``mine`` tells them apart)."""
        return ref[self._at(rows)]

    def _own(self, shape):
        """The lanes of a (rows, width) block that are the head's."""
        if self._tile is not None and self._tile.shape == shape:
            return self._tile
        d, pack = self.heads.d, self.heads.pack
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        place = jax.lax.shift_right_logical(        # D divides 128: 2 ** n
            lane, jax.lax.full(shape, d.bit_length() - 1, jnp.int32))
        return jax.lax.eq(place, jax.lax.broadcast(
            jax.lax.rem(self.index, pack), shape))

    def mine(self, x):
        """``x`` of ``load`` with the other heads' lanes selected to zero:
        for ONE operand of a product over lanes, which then is this head's
        alone."""
        if self.heads.pack <= 1:
            return x
        return jax.lax.select(self._own(x.shape), x, jax.lax.full_like(x, 0))

    def store(self, ref, x):
        """``x``, right in the head's lanes and anything in the others, as
        this head of ``ref``: the other heads' lanes of the block keep what
        they hold."""
        at = self._at(slice(None))
        if self.heads.pack > 1:
            x = jax.lax.select(self._own(x.shape), x, ref[at])
        ref[at] = x


def _pack(h, group, d, align):
    """``_Heads.pack`` for ``h`` query heads of size ``d``: 1 where a head is
    whole lane blocks, the heads of a lane block where ``d`` divides it and
    they divide ``h`` (blocks ``align``-ed: 128 lanes compiled, anything
    interpreted), else 0, the folded layout."""
    if d % _LANES == 0:
        return 1
    if _LANES % d or group > 1:
        return 0
    pack = _pick_block(h, _LANES // d)
    return pack if pack * d % align == 0 else 0


def _vmem_bytes(heads, g, sq, sk, itemsize, block_q, block_k):
    """VMEM a program of ``g`` heads is reckoned to need, over the three
    kernels: the resident rows (K and V, or Q, dO and O with their row
    statistic) and the tiled operands and results, double-buffered, plus two
    f32 slabs of one head's intermediates.  In the projections' layout a head's rows
    are ``D`` lanes wide, whatever ``D``; folded, a block's minor dimension
    is one head's and is padded to 128 lanes (at D = 64: twice the bytes).
    About a sixth above what the compiler reports at the benchmark's GPT
    shape (9.70 MB against 8.44 at 4 heads a program)."""
    s, b = max(sq, sk), max(block_q, block_k)
    lanes = heads.d if heads.pack else -(-heads.d // _LANES) * _LANES
    resident = 2 * g * s * (3 * lanes * itemsize + 8 * 4)
    tiles = 2 * 4 * g * b * lanes * itemsize
    work = 2 * 4 * b * (s if _prefix(False, sq, sk, b, s) else b)
    return resident + tiles + work


def _pick_heads(heads, n, sq, sk, itemsize, block_q, block_k):
    """Heads per program: the largest divisor up to ``_MAX_HEADS`` of the
    ``n`` heads that may go together, in whole lane blocks, whose program
    fits the VMEM budget; the least such group if that alone fits the
    raised limit; 0 when not even that."""
    unit = max(heads.pack, 1)
    need = functools.partial(_vmem_bytes, heads, sq=sq, sk=sk,
                             itemsize=itemsize, block_q=block_q,
                             block_k=block_k)
    g = _pick_block(n, _MAX_HEADS, unit) or unit
    while g > unit and need(g) > _VMEM_BUDGET:
        g = _pick_block(n, g - unit, unit)
    return g if need(g) <= (_VMEM_BUDGET if g > unit
                            else _VMEM_LIMIT * 3 // 4) else 0


def _together(heads, bh, h, group, biased):
    """The heads that may go into one program: those of one K/V head under
    grouped K/V heads, else those of one example in the projections' layout
    or when a per-example bias rides along, else the whole fold."""
    if group > 1:
        return group
    return h if heads.pack or biased else bh


def _on_tpu():
    return jax.default_backend() == "tpu"


def _kv_index(b, h, group):
    """Fold index of the K/V head shared by q-fold index ``b`` (GQA): the
    q fold is batch-major over h query heads, the kv fold over h//group
    kv heads; query head hq reads kv head hq // group."""
    return (b // h) * (h // group) + (b % h) // group


def use_flash(impl):
    """Resolve a model config's ``attention_impl`` value at trace time:
    "auto" -> this kernel on TPU, the XLA path elsewhere."""
    if impl == "flash":
        return True
    if impl == "xla":
        return False
    if impl != "auto":
        raise ValueError(f"attention_impl must be auto|flash|xla, got {impl!r}")
    return _on_tpu()


def _xla_attention(q, k, v, causal, kv_mask, sm_scale):
    """Fallback for shapes the compiled kernel cannot tile (Mosaic wants
    128-lane-aligned blocks); also keeps odd-length prototypes working."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, _NEG_INF)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        m = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(m[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if kv_mask is not None:  # fully-masked rows: match the kernel's exact 0
        p = jnp.where(jnp.any(kv_mask, axis=-1)[:, None, None, None], p, 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# ------------------------------------------------------- the tile program --
#
# A program takes G heads and one tile of rows (q rows for the forward, dq
# and the ring update; k rows for dk/dv), walks its heads in a loop, and
# for each visits the SLABS of the other sequence that the tile sees:
#
#   * "prefix" form (flash path at moderate S): one slab a head, the whole
#     visible prefix (or suffix), whose length is static per tile index (a
#     ``pl.when`` chain over the tile index).  No running state: the result
#     of the one slab is the result.
#   * "loop" form (long rows, and ring attention's traced offsets): slabs of
#     one block, first those the diagonal does not touch, then those it
#     crosses; partial results are merged into VMEM scratch.

def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _prescale(q, sm_scale):
    """``(q', scale left for the scores)``: a power-of-two softmax scale is
    folded into q, where it is exact in every float dtype and costs a
    (rows, D) multiply instead of a (rows, cols) one per slab."""
    if math.frexp(sm_scale)[0] == 0.5:
        return q * jnp.asarray(sm_scale, q.dtype), 1.0
    return q, sm_scale


def _at(start, size, block):
    if not isinstance(start, int):
        start = pl.multiple_of(start, block)
    return pl.ds(start, size)


def _lanes(x, n):
    """(rows, 128) with all lanes equal -> (rows, n): whole vregs repeated
    where n is a multiple of the lanes, else a lane broadcast."""
    w = x.shape[-1]
    if n % w == 0:
        return x if n == w else jnp.concatenate([x] * (n // w), axis=-1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _to_row(col):
    """(rows, 1) -> (1, rows), through a lane-dense square transpose (a
    reshape would relayout element by element)."""
    return jnp.broadcast_to(col, (col.shape[0], _LANES)).T[:1, :]


def _to_dense(row):
    """(1, rows) -> (rows, 128) with all lanes equal."""
    return jnp.broadcast_to(row, (_LANES, row.shape[-1])).T


def _scores(a, b, bias, scale, mask, q_rows):
    """f32 score slab ``a @ b^T``, (rows of a, rows of b); shared by the fwd,
    ring-update and both bwd kernels so recomputation matches the forward
    bit-for-bit.  ``mask=(width, bound, leading)``: of the ``width`` leading
    or trailing columns, keep the entries whose q position minus k position
    inside that block is >= bound (the causal triangle at the block's global
    place); ``q_rows`` says which axis is q."""
    s = _dot(a, b, _NT)
    if scale != 1.0:
        s = s * scale
    if bias is not None:
        s = s + bias
    if mask is not None:
        width, bound, leading = mask
        r = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], width), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], width), 1)
        keep = ((r - c) if q_rows else (c - r)) >= bound
        if width == s.shape[1]:
            s = jnp.where(keep, s, _NEG_INF)
        elif leading:
            s = jnp.concatenate(
                [jnp.where(keep, s[:, :width], _NEG_INF), s[:, width:]], 1)
        else:
            s = jnp.concatenate(
                [s[:, :-width], jnp.where(keep, s[:, -width:], _NEG_INF)], 1)
    return s


def _softmax_slab(s, v):
    """Softmax partials of one score slab: row max, denominator and
    unnormalized output against that max.  m, l: (rows, 1); acc: (rows, D);
    all f32.  The single shared implementation for the fwd kernel and the
    ring block-update kernel."""
    m = jnp.maximum(jnp.max(s, axis=-1, keepdims=True), _M_FLOOR)
    p = jnp.exp(s - m)
    return m, jnp.sum(p, axis=-1, keepdims=True), \
        _dot(p.astype(v.dtype), v, _NN)


def _k_bounds(q_start, k_off, block_q, block_k, nk, causal):
    """``(n_full, n_vis)`` for the q tile that starts at global row
    ``q_start``: k tiles [0, n_full) lie wholly at or below its diagonal,
    [n_full, n_vis) cross it, the rest is masked out."""
    if not causal:
        return nk, nk
    return (jnp.clip((q_start - k_off + 1) // block_k, 0, nk),
            jnp.clip((q_start + block_q - 1 - k_off) // block_k + 1, 0, nk))


def _q_bounds(k_start, q_off, block_q, block_k, nq, causal):
    """``(i_vis, i_full)`` for the k tile that starts at global column
    ``k_start``: q tiles [i_vis, i_full) cross its diagonal, [i_full, nq) lie
    wholly below it, those before i_vis are masked out."""
    if not causal:
        return 0, 0
    return (jnp.clip((k_start - q_off) // block_q, 0, nq),
            jnp.clip(-((q_off - (k_start + block_k - 1)) // block_q), 0, nq))


def _prefix(causal, sq, sk, rows, cols_total):
    """Whether a program visits its whole visible prefix as ONE slab: its
    length must be static per tile index (no offsets: the callers' business;
    causal only on a square), the chain of cases short, and the f32 slab
    intermediates within their budget."""
    return ((not causal or sq == sk) and cols_total // rows <= _MAX_CASES
            and 6 * 4 * rows * cols_total <= _SLAB_BUDGET)


def _cases(prefix, causal, block, total, leading):
    """The static slabs of the prefix form, one per tile index (a single one
    serves every tile), or None for the loop form.  Causal: a q tile sees K
    up to its own end, masked on the trailing block; a k tile (``leading``)
    sees Q from its own start, masked on the leading block."""
    if not prefix:
        return None
    if not causal:
        return [(0, total, None)]
    if leading:
        return [(c * block, total - c * block, (block, 0, True))
                for c in range(total // block)]
    return [(0, (c + 1) * block, (block, 0, False))
            for c in range(total // block)]


def _visit(heads, rows, tile, g, cases, direct, looped):
    """Walk a program's ``g`` heads, each a ``_Head`` ``h`` over tiles of
    ``rows`` rows: ``direct(h, lo, size, mask)`` on the static slab of this
    tile index (prefix form), else ``looped(h)``."""
    def walk(body):
        jax.lax.fori_loop(
            0, g, lambda i, c: body(_Head(heads, i, rows)) or c, 0)

    if cases is None:
        walk(looped)
    elif len(cases) == 1:
        walk(lambda h: direct(h, *cases[0]))
    else:
        for c, slab in enumerate(cases):
            pl.when(tile == c)(functools.partial(
                walk, lambda h, slab=slab: direct(h, *slab)))


def _loop_k(slab, q_start, k_off, block_q, block_k, nk, causal):
    """Loop form of a q tile: ``slab(lo, size, mask)`` over the k blocks
    below its diagonal, then (causal) over those the diagonal crosses."""
    n_full, n_vis = _k_bounds(q_start, k_off, block_q, block_k, nk, causal)
    jax.lax.fori_loop(
        0, n_full, lambda j, c: slab(j * block_k, block_k, None) or c, 0)
    if causal:
        jax.lax.fori_loop(
            n_full, n_vis, lambda j, c: slab(j * block_k, block_k, (
                block_k, k_off + j * block_k - q_start, False)) or c, 0)


# ---------------------------------------------------------------- forward --

def _fwd_kernel(*refs, heads, has_bias, sm_scale, causal, block_k, prefix):
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    o_ref, lse_ref = refs[3 + has_bias:5 + has_bias]
    m_scr, l_scr, acc_scr = refs[5 + has_bias:]
    g, g_kv = heads.count(o_ref), heads.count(k_ref)
    block_q, sk = q_ref.shape[1], k_ref.shape[1]
    tile, kv = pl.program_id(heads.tile_axis), _Head(heads, 0)

    def rows(h):
        return _prescale(h.mine(h.load(q_ref)), sm_scale)

    def part(h, q, scale, lo, size, mask):
        at, hk = _at(lo, size, block_k), h if g_kv == g else kv
        bias = None if bias_ref is None else bias_ref[0, :, at]
        s = _scores(q, hk.load(k_ref, at), bias, scale, mask, True)
        return _softmax_slab(s, hk.load(v_ref, at))

    def finish(h, m, l, acc):
        denom = jnp.where(l == 0.0, 1.0, l)            # fully-masked rows -> 0
        h.store(o_ref, (acc * (1.0 / denom)).astype(o_ref.dtype))
        lse_ref[h.index] = _to_row(m + jnp.log(denom))

    def looped(h):
        q = rows(h)
        m_scr[...] = jnp.full_like(m_scr, _M_FLOOR)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        _loop_k(lambda *slab: _merge(m_scr, l_scr, acc_scr,
                                     *part(h, *q, *slab)),
                tile * block_q, 0, block_q, block_k, sk // block_k, causal)
        finish(h, m_scr[:, :1], l_scr[:, :1], acc_scr[...])

    _visit(heads, block_q, tile, g,
           _cases(prefix, causal, block_q, sk, False),
           lambda h, *slab: finish(h, *part(h, *rows(h), *slab)), looped)


def _merge(m_scr, l_scr, acc_scr, m, l, acc):
    """Fold the softmax partials of one slab into the running ones."""
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, m)
    a, b = jnp.exp(m_prev - m_new), jnp.exp(m - m_new)[:, :1]
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * a + l * b
    acc_scr[...] = acc_scr[...] * a[:, :1] + acc * b


def _tpu_params(vmem_bytes, grid_rank=2):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * grid_rank,
        vmem_limit_bytes=_VMEM_LIMIT if vmem_bytes > _VMEM_BUDGET else None)


def _softmax_scratch(block_q, width):
    return [pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, width), jnp.float32)]    # output accumulator


class _Plan(typing.NamedTuple):
    """What a call's three kernels share, all of it static."""
    heads: _Heads
    h: int              # query heads an example
    group: int          # of them to a K/V head
    g: int              # heads a program
    block_q: int
    block_k: int
    sm_scale: float
    causal: bool
    interpret: bool
    # the lane at which k and v start in their operand: (0, 0), or where
    # they lie in a packed qkv that is passed for all three
    bases: tuple = (0, 0)


def _specs(plan, sq, sk, q_major):
    """BlockSpecs and grid of a program of ``plan.g`` query heads.
    ``q_major``: the grid ends in q tiles and K/V are resident rows; else it
    ends in k tiles and Q's side is resident.  In the projections' layout the
    grid is (B, head groups, tiles) over (B, rows, heads * D) tensors; folded,
    (head groups, tiles) over (B * heads, rows, D).  Returns the spec of a
    q-side tensor, the maker of a K/V spec (at the lane where K or V starts
    in its operand), the spec of the (B, 1, sk) bias, of a (B*H, 1, sq) row
    statistic and of a per-q-head dk/dv, and the grid for ``n`` leading
    entries of q."""
    heads, g, h, group = plan.heads, plan.g, plan.h, plan.group
    d, ax = heads.d, heads.tile_axis
    if q_major:
        rows_q, rows_k, tiles = plan.block_q, sk, sq // plan.block_q
        at_q, at_k = (lambda i: i[ax]), (lambda i: 0)
    else:
        rows_q, rows_k, tiles = sq, plan.block_k, sk // plan.block_k
        at_q, at_k = (lambda i: 0), (lambda i: i[ax])
    # bias rides as (B, 1, Sk): Mosaic wants the last TWO block dims
    # divisible by (8, 128) or equal to the array's
    if heads.pack:
        # g divides the group: one shared K/V head per program
        kv_lanes = g * d if group == 1 else d
        kv_at = (lambda i: i[1]) if group == 1 else \
            (lambda i: i[1] * g // group)
        return (
            pl.BlockSpec((1, rows_q, g * d),
                         lambda *i: (i[0], at_q(i), i[1])),
            lambda lane=0: pl.BlockSpec(
                (1, rows_k, kv_lanes),
                lambda *i: (i[0], at_k(i), lane // kv_lanes + kv_at(i))),
            pl.BlockSpec((1, 1, rows_k), lambda *i: (i[0], 0, at_k(i))),
            pl.BlockSpec((g, 1, rows_q),
                         lambda *i: (i[0] * (h // g) + i[1], 0, at_q(i))),
            pl.BlockSpec((1, rows_k, g * d),
                         lambda *i: (i[0], at_k(i), i[1])),
            lambda n: (n, h // g, tiles))
    if group == 1:
        g_kv, kv_at = g, lambda i: i[0]
    else:       # g divides the group: one shared K/V head per program
        g_kv, kv_at = 1, lambda i: _kv_index(i[0] * g, h, group)
    return (
        pl.BlockSpec((g, rows_q, d), lambda *i: (i[0], at_q(i), 0)),
        lambda lane=0: pl.BlockSpec(
            (g_kv, rows_k, d), lambda *i: (kv_at(i), at_k(i), 0)),
        pl.BlockSpec((1, 1, rows_k), lambda *i: (i[0] * g // h, 0, at_k(i))),
        pl.BlockSpec((g, 1, rows_q), lambda *i: (i[0], 0, at_q(i))),
        pl.BlockSpec((g, rows_k, d), lambda *i: (i[0], at_k(i), 0)),
        lambda n: (n // g, tiles))


def _params(plan, sq, sk, itemsize):
    """Mosaic's parameters for a call."""
    return _tpu_params(
        _vmem_bytes(plan.heads, plan.g, sq, sk, itemsize, plan.block_q,
                    plan.block_k), plan.heads.tile_axis + 1)


def _out_lanes(plan):
    """Minor dimension of a q-side result."""
    return plan.h * plan.heads.d if plan.heads.pack else plan.heads.d


def _flash_fwd(q, k, v, bias, plan):
    """Projections' layout: q (B, Sq, H*D), k and v (B, Sk, H/group*D), or
    all three the one packed (B, S, (H + 2H/group)*D) with ``plan.bases``;
    folded: q (B*H, Sq, D), k and v (B*H/group, Sk, D).  Grouped K/V heads
    read the shared K/V row straight from HBM via the index map, never
    materializing repeats.  bias: (B, Sk) f32 or None.  Returns (out, lse):
    out in q's layout, lse (B*H, 1, Sq) f32."""
    n, sq, sk = q.shape[0], q.shape[1], k.shape[1]
    qspec, kspec, bspec, row, _, grid = _specs(plan, sq, sk, q_major=True)
    biased = [] if bias is None else [(bspec, bias[:, None, :])]
    stats = n * plan.h if plan.heads.pack else n
    return pl.pallas_call(
        functools.partial(_fwd_kernel, heads=plan.heads,
                          has_bias=bool(biased), sm_scale=plan.sm_scale,
                          causal=plan.causal, block_k=plan.block_k,
                          prefix=_prefix(plan.causal, sq, sk, plan.block_q,
                                         sk)),
        grid=grid(n),
        in_specs=[qspec, kspec(plan.bases[0]), kspec(plan.bases[1])]
        + [s for s, _ in biased],
        out_specs=[qspec, row],
        out_shape=[
            jax.ShapeDtypeStruct((n, sq, _out_lanes(plan)), q.dtype),
            jax.ShapeDtypeStruct((stats, 1, sq), jnp.float32),
        ],
        scratch_shapes=_softmax_scratch(plan.block_q, plan.heads.width),
        compiler_params=_params(plan, sq, sk, q.dtype.itemsize),
        interpret=plan.interpret,
    )(q, k, v, *[a for _, a in biased])


# --------------------------------------------------------------- backward --

def _delta(do, out):
    """``rowsum(dO * O)`` of one head, a (rows, 1) f32 column: what the score
    gradient subtracts from dP.  ``do`` carries zeros in the other heads'
    lanes.  Taken in the kernels, from the tiles they hold: XLA can make it
    only by laying (B, S, H*D) out anew (PERF.md, PR 31)."""
    return jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1, keepdims=True)


def _bwd_refs(refs, has_bias, n_out):
    """(q, k, v, bias or None, do, out, lse, outs, scratch) of a backward
    kernel."""
    bias_ref = refs[3] if has_bias else None
    rest = refs[3 + has_bias:]
    return refs[:3] + (bias_ref,) + rest[:3] + (rest[3:3 + n_out],
                                                rest[3 + n_out:])


def _dq_kernel(qoff_ref, koff_ref, *refs, heads, has_bias, sm_scale, causal,
               block_k, prefix):
    (q_ref, k_ref, v_ref, bias_ref, do_ref, out_ref, lse_ref, (dq_ref,),
     (dq_scr,)) = _bwd_refs(refs, has_bias, 1)
    g, g_kv = heads.count(dq_ref), heads.count(k_ref)
    block_q, sk = q_ref.shape[1], k_ref.shape[1]
    tile, kv = pl.program_id(heads.tile_axis), _Head(heads, 0)

    def rows(h):     # what the slabs of a head share
        do = h.mine(h.load(do_ref))
        return (*_prescale(h.mine(h.load(q_ref)), sm_scale),
                do, _to_dense(lse_ref[h.index]),
                jnp.broadcast_to(_delta(do, h.load(out_ref)),
                                 (block_q, _LANES)))

    def part(h, q, scale, do, lse, delta, lo, size, mask):
        at, hk = _at(lo, size, block_k), h if g_kv == g else kv
        k = hk.load(k_ref, at)
        bias = None if bias_ref is None else bias_ref[0, :, at]
        p = jnp.exp(_scores(q, k, bias, scale, mask, True)
                    - _lanes(lse, size))                        # (bq, size)
        dp = _dot(do, hk.load(v_ref, at), _NT)                  # dO @ v^T
        ds = p * (dp - _lanes(delta, size))
        return _dot(ds.astype(k.dtype), k, _NN)                 # ds @ k

    def finish(h, dq):
        h.store(dq_ref, (dq * sm_scale).astype(dq_ref.dtype))

    def looped(h):
        shared = rows(h)
        dq_scr[...] = jnp.zeros_like(dq_scr)

        def slab(*a):
            dq_scr[...] += part(h, *shared, *a)

        _loop_k(slab, qoff_ref[0] + tile * block_q, koff_ref[0], block_q,
                block_k, sk // block_k, causal)
        finish(h, dq_scr[...])

    _visit(heads, block_q, tile, g,
           _cases(prefix, causal, block_q, sk, False),
           lambda h, *slab: finish(h, part(h, *rows(h), *slab)), looped)


def _dkdv_kernel(qoff_ref, koff_ref, *refs, heads, has_bias, sm_scale, causal,
                 block_q, prefix):
    """Scores are kept transposed, (k rows, q columns): both products into
    dk and dv are then plain matmuls, and the row statistics are used as
    the (1, q) rows they are stored as."""
    (q_ref, k_ref, v_ref, bias_ref, do_ref, out_ref, lse_ref,
     (dk_ref, dv_ref), (dk_scr, dv_scr)) = _bwd_refs(refs, has_bias, 2)
    g, g_kv = heads.count(dk_ref), heads.count(k_ref)
    sq, block_k = q_ref.shape[1], k_ref.shape[1]
    tile, kv = pl.program_id(heads.tile_axis), _Head(heads, 0)

    def part(h, lo, size, mask):
        at, hk = _at(lo, size, block_q), h if g_kv == g else kv
        q, scale = _prescale(h.load(q_ref, at), sm_scale)
        do = h.load(do_ref, at)
        k, v = h.mine(hk.load(k_ref)), h.mine(hk.load(v_ref))
        bias = None if bias_ref is None else \
            _lanes(_to_dense(bias_ref[0]), size)
        pt = jnp.exp(_scores(k, q, bias, scale, mask, False)
                     - lse_ref[h.index, :, at])                 # (bk, size)
        dv = _dot(pt.astype(do.dtype), do, _NN)                 # p^T @ dO
        delta = _to_row(_delta(h.mine(do), h.load(out_ref, at)))
        dst = pt * (_dot(v, do, _NT) - delta)
        dk = _dot(dst.astype(q.dtype), q, _NN)                  # ds^T @ q
        return (dk if scale == 1.0 else dk * sm_scale), dv      # q' carried it

    def finish(h, dk, dv):
        h.store(dk_ref, dk.astype(dk_ref.dtype))
        h.store(dv_ref, dv.astype(dv_ref.dtype))

    def looped(h):
        q_off, k_start = qoff_ref[0], koff_ref[0] + tile * block_k
        i_vis, i_full = _q_bounds(k_start, q_off, block_q, block_k,
                                  sq // block_q, causal)
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

        def slab(i, mask):
            dk, dv = part(h, i * block_q, block_q, mask)
            dk_scr[...] += dk
            dv_scr[...] += dv

        if causal:
            jax.lax.fori_loop(
                i_vis, i_full, lambda i, c: slab(i, (
                    block_q, k_start - q_off - i * block_q, True)) or c, 0)
        jax.lax.fori_loop(i_full, sq // block_q,
                          lambda i, c: slab(i, None) or c, 0)
        finish(h, dk_scr[...], dv_scr[...])

    _visit(heads, block_k, tile, g,
           _cases(prefix, causal, block_k, sq, True),
           lambda h, *slab: finish(h, *part(h, *slab)), looped)


def _offsets(q_off, k_off):
    return (jnp.asarray(q_off, jnp.int32).reshape(1),
            jnp.asarray(k_off, jnp.int32).reshape(1))


def _bwd_call(kernel, q_major, q, k, v, bias, do, out, lse, plan,
              q_off=None, k_off=None):
    """One backward kernel: dq (``q_major``: a q tile against K's row) or
    dk/dv (a k tile against Q's rows), operands and ``out`` as ``_flash_fwd``
    has them, ``lse`` (B*H, 1, Sq).  Offsets (ring attention's traced
    block starts) place the two rows globally and force the loop form."""
    n, sq, sk = q.shape[0], q.shape[1], k.shape[1]
    qspec, kspec, bspec, row, per_q, grid = _specs(plan, sq, sk, q_major)
    biased = [] if bias is None else [(bspec, bias[:, None, :])]
    lanes, width = _out_lanes(plan), plan.heads.width
    if q_major:
        tile, other, total = plan.block_q, {"block_k": plan.block_k}, sk
        out_specs = qspec
        out_shape = jax.ShapeDtypeStruct((n, sq, lanes), q.dtype)
        scratch = [(plan.block_q, width)]
    else:
        tile, other, total = plan.block_k, {"block_q": plan.block_q}, sq
        # group > 1: per-q-head partials stay f32 so the cross-head group
        # sum keeps the kernel's f32 accumulation (cast once, after)
        out_specs, out_shape = [per_q, per_q], [
            jax.ShapeDtypeStruct(
                (n, sk, lanes), jnp.float32 if plan.group > 1 else t.dtype)
            for t in (k, v)]
        scratch = [(plan.block_k, width)] * 2
    prefix = q_off is None and _prefix(plan.causal, sq, sk, tile, total)
    return pl.pallas_call(
        functools.partial(kernel, heads=plan.heads, has_bias=bool(biased),
                          sm_scale=plan.sm_scale, causal=plan.causal,
                          prefix=prefix, **other),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid(n),
            in_specs=[qspec, kspec(plan.bases[0]), kspec(plan.bases[1])]
            + [s for s, _ in biased] + [qspec, qspec, row],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch]),
        out_shape=out_shape,
        compiler_params=_params(plan, sq, sk, q.dtype.itemsize),
        interpret=plan.interpret,
    )(*_offsets(0 if q_off is None else q_off, 0 if k_off is None else k_off),
      q, k, v, *[a for _, a in biased], do, out, lse)


def _fold_plan(q, k, h, bias, sm_scale, causal, block_q, block_k, interpret):
    """The plan of a call on folded operands, one K/V head a query head."""
    heads = _Heads(q.shape[2], 0)
    g = _pick_heads(heads, _together(heads, q.shape[0], h, 1,
                                     bias is not None),
                    q.shape[1], k.shape[1], q.dtype.itemsize, block_q,
                    block_k)
    assert g, "flash kernels: not even one head's rows fit the VMEM limit"
    return _Plan(heads, h, 1, g, block_q, block_k, sm_scale, causal,
                 interpret)


def _dq_call(q, k, v, bias, do, out, lse, h, sm_scale, causal,
             block_q, block_k, interpret, q_off=None, k_off=None):
    """dq of folded q (B*H, Sq, D) against one K/V row; ``lse`` (B*H, Sq).
    Ring attention's backward step."""
    plan = _fold_plan(q, k, h, bias, sm_scale, causal, block_q, block_k,
                      interpret)
    return _bwd_call(_dq_kernel, True, q, k, v, bias, do, out,
                     lse[:, None, :], plan, q_off, k_off)


def _dkdv_call(q, k, v, bias, do, out, lse, h, sm_scale, causal,
               block_q, block_k, interpret, q_off=None, k_off=None):
    """(dk, dv) of one folded K/V row from all local q rows, as
    ``_dq_call``."""
    plan = _fold_plan(q, k, h, bias, sm_scale, causal, block_q, block_k,
                      interpret)
    return _bwd_call(_dkdv_kernel, False, q, k, v, bias, do, out,
                     lse[:, None, :], plan, q_off, k_off)


def _flash_bwd(q, k, v, bias, out, lse, do, plan):
    """(dq, dk, dv) in the operands' layout.  Under grouped K/V heads the
    dk/dv kernel's results are PER-Q-HEAD (grid writes must not alias across
    the parallel head dimension) and are summed here down to the K/V heads."""
    n, sk = q.shape[0], k.shape[1]
    h, group, d = plan.h, plan.group, plan.heads.d
    dq = _bwd_call(_dq_kernel, True, q, k, v, bias, do, out, lse, plan)
    dk, dv = _bwd_call(_dkdv_kernel, False, q, k, v, bias, do, out, lse,
                       plan)
    if group > 1 and plan.heads.pack:
        # per-q-head contributions -> sum each kv-head group, as sums of lane
        # slices (a reduction over a reshaped minor dimension is laid out
        # anew by XLA: two copies of a float32 (B, S, H*D))
        def group_sum(t, like):
            parts = [t[..., i * d:(i + 1) * d] for i in range(h)]
            return jnp.concatenate(
                [sum(parts[i:i + group][1:], parts[i])
                 for i in range(0, h, group)], axis=-1).astype(like.dtype)

        dk, dv = group_sum(dk, k), group_sum(dv, v)
    elif group > 1:
        shape = (n // h, h // group, group, sk, d)
        dk = dk.reshape(shape).sum(2).reshape(k.shape).astype(k.dtype)
        dv = dv.reshape(shape).sum(2).reshape(v.shape).astype(v.dtype)
    return dq, dk, dv


# ------------------------------------------------- ring-attention carry op --

def _block_update_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                         m_in_ref, l_in_ref, o_in_ref,
                         m_out_ref, l_out_ref, o_out_ref,
                         m_scr, l_scr, acc_scr, *, heads, sm_scale, causal,
                         block_k):
    """One ring-attention step: fold a remote K/V block into the running
    (m, l, o) online-softmax carry.  The forward kernel's loop form, but the
    accumulator state enters and leaves through HBM (it is a lax.scan carry
    in ``parallel/ring_attention.py``), and causal masking is over GLOBAL
    positions (q_off / k_off scalars = ring block starts)."""
    g, block_q, _ = q_ref.shape
    tile = pl.program_id(heads.tile_axis)

    def looped(head):
        h = head.index
        q, scale = _prescale(q_ref[h], sm_scale)
        # clamp at the floor: the XLA ring path seeds m with -inf, under
        # which exp(m_prev - m_new) would NaN at the first real block
        m_scr[...] = jnp.maximum(_to_dense(m_in_ref[h]), _M_FLOOR)
        l_scr[...] = _to_dense(l_in_ref[h])
        acc_scr[...] = o_in_ref[h]

        def slab(lo, size, mask):
            at = _at(lo, size, block_k)
            s = _scores(q, k_ref[h, at, :], None, scale, mask, True)
            _merge(m_scr, l_scr, acc_scr, *_softmax_slab(s, v_ref[h, at, :]))

        _loop_k(slab, qoff_ref[0] + tile * block_q, koff_ref[0], block_q,
                block_k, k_ref.shape[1] // block_k, causal)
        m_out_ref[h] = _to_row(m_scr[:, :1])
        l_out_ref[h] = _to_row(l_scr[:, :1])
        o_out_ref[h] = acc_scr[...]

    _visit(heads, block_q, tile, g, None, None, looped)


def flash_block_update(q, k, v, m, l, o, q_off, k_off, causal=False,
                       sm_scale=None, block_q=DEFAULT_BLOCK_Q,
                       block_k=DEFAULT_BLOCK_K, interpret=None):
    """Flash-tiled online-softmax block update for ring attention.

    Args (all per-device local, inside shard_map):
      q: (BH, Sq, D); k, v: (BH, Sk, D) — the K/V block currently streaming
        through this device; m, l: (BH, Sq) f32 running max / denominator;
      o: (BH, Sq, D) f32 UNNORMALIZED output accumulator;
      q_off, k_off: traced int32 global start positions of the q block and
        this ring step's K/V block (causal masks global positions).

    Returns updated (m, l, o).  Returns None when the shapes cannot be
    tiled for the compiled kernel — caller falls back to the XLA update.
    """
    if interpret is None:
        interpret = not _on_tpu()
    bh, sq, d = q.shape
    sk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    align = 1 if interpret else _LANES
    bq = _pick_block(sq, block_q, align)
    bk = _pick_block(sk, block_k, align)
    heads = _Heads(d, 0)
    g = bq and bk and _pick_heads(heads, bh, sq, sk, q.dtype.itemsize, bq, bk)
    if not g:
        return None
    plan = _Plan(heads, bh, 1, g, bq, bk, float(sm_scale), bool(causal),
                 interpret)
    qspec, kspec, _, row, _, grid = _specs(plan, sq, sk, q_major=True)
    m2, l2, o2 = pl.pallas_call(
        functools.partial(_block_update_kernel, heads=heads,
                          sm_scale=plan.sm_scale, causal=plan.causal,
                          block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=grid(bh),
            in_specs=[qspec, kspec(), kspec(), row, row, qspec],
            out_specs=[row, row, qspec],
            scratch_shapes=_softmax_scratch(bq, d)),
        out_shape=[
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        ],
        compiler_params=_params(plan, sq, sk, q.dtype.itemsize),
        interpret=interpret,
    )(*_offsets(q_off, k_off), q, k, v, m[:, None, :], l[:, None, :],
      o.astype(jnp.float32))
    return m2[:, 0, :], l2[:, 0, :], o2


# ------------------------------------------------------------- public API --

@functools.lru_cache(maxsize=64)
def _make_flash(plan):
    """The differentiable call of a plan.  ``qkv``: ``(q, k, v)``, or the one
    packed array of ``plan.bases``, which the kernels then read three
    times; the cotangent comes back in the same form."""
    def three(qkv):
        return qkv * 3 if len(qkv) == 1 else qkv

    @jax.custom_vjp
    def attend(qkv, bias):
        return _flash_fwd(*three(qkv), bias, plan)[0]

    def fwd(qkv, bias):
        out, lse = _flash_fwd(*three(qkv), bias, plan)
        return out, (qkv, bias, out, lse)

    def bwd(res, do):
        qkv, bias, out, lse = res
        grads = _flash_bwd(*three(qkv), bias, out, lse, do, plan)
        if len(qkv) == 1:
            grads = (jnp.concatenate(grads, axis=-1),)
        return grads, None if bias is None else jnp.zeros_like(bias)

    attend.defvjp(fwd, bwd)
    return attend


def _plan(q_shape, h_kv, sk, dtype, causal, masked, sm_scale, block_q,
          block_k, interpret):
    """The plan for q ``(B, Sq, H, D)`` on ``h_kv`` K/V heads over ``sk``
    keys, or None where no kernel serves the shapes (said once)."""
    b, sq, h, d = q_shape
    if h % h_kv:
        raise ValueError(f"query heads {h} not a multiple of kv heads {h_kv}")
    group = h // h_kv
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    # compiled Mosaic wants 128-lane-aligned blocks (the lse/bias specs put
    # block_q/block_k in the minor dim); the interpreter accepts anything
    align = 1 if interpret else _LANES
    bq = _pick_block(sq, block_q, align)
    bk = _pick_block(sk, block_k, align)
    heads = _Heads(d, _pack(h, group, d, align))
    itemsize = jnp.dtype(dtype).itemsize
    g = bq and bk and _pick_heads(
        heads, _together(heads, b * h, h, group, masked), sq, sk, itemsize,
        bq, bk)
    shapes = (tuple(q_shape), (b, sk, h_kv, d))
    if not g:
        logging.warning_once(
            "flash_attention q%s k%s: no %d-aligned block divides the "
            "sequence, or one head's rows pass the VMEM budget; running XLA "
            "attention at this site", *shapes, align)
        return None
    logging.info_once(
        "flash_attention q%s k%s %s: %s, %d heads a program, block_q %d, "
        "block_k %d, %s, %d bytes of VMEM reckoned", *shapes,
        str(jnp.dtype(dtype)),
        "folded to (B*H, S, D) by a transpose" if not heads.pack else
        "read as (B, S, H*D), %d heads a lane block" % heads.pack,
        g, bq, bk,
        "the visible prefix in one slab" if _prefix(causal, sq, sk, bq, sk)
        else "at most %d k tiles a q tile in a loop" % (sk // bk),
        _vmem_bytes(heads, g, sq, sk, itemsize, bq, bk))
    return _Plan(heads, h, group, g, bq, bk, float(sm_scale), bool(causal),
                 bool(interpret))


def _bias(kv_mask):
    return None if kv_mask is None else \
        jnp.where(kv_mask, 0.0, _NEG_INF).astype(jnp.float32)


def flash_attention(q, k, v, causal=False, kv_mask=None, sm_scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=None):
    """Flash attention over (B, S, H, D) tensors (the model layout of
    ``models/gpt.py`` / ``models/bert.py``).  Differentiable (custom VJP);
    O(S) attention memory; causal masks over in-kernel iotas.

    ``kv_mask``: optional (B, S_k) boolean key-validity mask (False = padded
    key, the BERT ``attention_mask``).  Fully-masked rows return exact 0.
    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere
    (the tests' CPU path).  ``block_q``/``block_k`` are upper bounds: tiles
    shrink to divisors of S, and the layout the kernels read (the operands'
    own, as (B, S, H*D), or folded by a transpose) and the heads a program
    takes follow from the shapes (``_pack``, ``_pick_heads``).
    """
    if interpret is None:
        interpret = not _on_tpu()
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    plan = _plan(q.shape, h_kv, sk, q.dtype, causal, kv_mask is not None,
                 sm_scale, block_q, block_k, interpret)
    if plan is None:
        if h > h_kv:
            k = jnp.repeat(k, h // h_kv, axis=2)
            v = jnp.repeat(v, h // h_kv, axis=2)
        return _xla_attention(q, k, v, causal, kv_mask,
                              d ** -0.5 if sm_scale is None else sm_scale)
    if plan.heads.pack:     # (B, S, H', D) -> (B, S, H'*D): no data moves
        def lay(t):
            return t.reshape(*t.shape[:2], -1)
    else:                   # (B, S, H', D) -> (B*H', S, D)
        def lay(t):
            return t.transpose(0, 2, 1, 3).reshape(-1, t.shape[1], d)

    out = _make_flash(plan)((lay(q), lay(k), lay(v)), _bias(kv_mask))
    if plan.heads.pack:
        return out.reshape(b, sq, h, d)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


def flash_attention_packed(qkv, num_heads, num_kv_heads=None, causal=False,
                           kv_mask=None, sm_scale=None,
                           block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                           interpret=None):
    """``flash_attention`` of the q, k and v that lie side by side in one
    (B, S, (H + 2 H_kv) * D) array, as a fused ``qkv`` projection writes
    them: ``num_heads`` query heads, then ``num_kv_heads`` key and as many
    value heads.  Returns (B, S, H, D).  Where the kernels read the
    projections' layout they take the array itself, three times, each at
    its lane offset, and no slice of it is ever made; elsewhere this is
    ``flash_attention`` of the three slices."""
    if interpret is None:
        interpret = not _on_tpu()
    h, h_kv = num_heads, num_kv_heads or num_heads
    b, s, lanes = qkv.shape
    d = lanes // (h + 2 * h_kv)
    if d * (h + 2 * h_kv) != lanes:
        raise ValueError(f"qkv of {lanes} lanes does not hold {h} + 2 x "
                         f"{h_kv} heads of one size")
    plan = _plan((b, s, h, d), h_kv, s, qkv.dtype, causal,
                 kv_mask is not None, sm_scale, block_q, block_k, interpret)
    if plan is None or not plan.heads.pack:
        q, k, v = (qkv[..., lo * d:hi * d].reshape(b, s, hi - lo, d)
                   for lo, hi in ((0, h), (h, h + h_kv),
                                  (h + h_kv, h + 2 * h_kv)))
        return flash_attention(q, k, v, causal, kv_mask, sm_scale, block_q,
                               block_k, interpret)
    plan = plan._replace(bases=(h * d, (h + h_kv) * d))
    out = _make_flash(plan)((qkv,), _bias(kv_mask))
    return out.reshape(b, s, h, d)
