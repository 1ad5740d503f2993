"""Pallas TPU flash attention (tiled online-softmax) with a custom VJP.

The XLA attention path (``jax.nn.dot_product_attention``) materializes the
(S, S) score matrix in HBM — O(S^2) memory traffic that caps context
length and starves the MXU at long S.  This kernel is the standard
flash-attention recipe laid out for the TPU memory hierarchy:

  * one program takes G heads of the folded batch*heads axis and, for
    them, one tile of q rows and the WHOLE K/V row in VMEM (the row is
    fetched once per G heads: its block index does not change across the q
    tiles).  The grid is (B*H / G, q tiles); the program walks its heads in
    a loop, one head's working set at a time.  A masked tile is neither
    fetched nor stepped over;
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
    ((B*H, 1, S) f32).  Where a kernel needs them as columns it goes through
    a lane-dense square transpose (``_to_row``/``_to_dense``): a reshape
    relayouts element by element;
  * ``G`` and the tiles follow from the shapes, the dtype and a VMEM budget
    (``_pick_heads``, ``_prefix``), by the divisor rule of ``_pick_block``;
    the tiling chosen is logged once per shape.

What bounds it on a v5e at D = 64 (LLO dumps of the deviceless compile,
PERF.md section 5): every matmul half-fills the MXU (contraction or output
width 64), the two backward kernels are MXU-bound at that, and the forward
is bound by the f32 softmax on the VPU.

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
_VMEM_LIMIT = 64 * 1024 * 1024
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


def _vmem_bytes(g, sq, sk, d, itemsize, block_q, block_k):
    """VMEM a program of ``g`` heads is reckoned to need, over the three
    kernels: the resident rows (K and V, or Q and dO with their statistics)
    and the tiled operands and results, double-buffered, plus two f32 slabs
    of one head's intermediates.  A fifth to a quarter above what the
    compiler reports at the benchmark's shapes."""
    s, b = max(sq, sk), max(block_q, block_k)
    lanes = -(-d // _LANES) * _LANES
    resident = 2 * 2 * g * s * (lanes * itemsize + 8 * 4)
    tiles = 2 * 4 * g * b * lanes * itemsize
    work = 2 * 4 * b * (s if _prefix(False, sq, sk, b, s) else b)
    return resident + tiles + work


def _pick_heads(bh, h, group, biased, sq, sk, d, itemsize, block_q, block_k):
    """Heads per program: the largest divisor up to ``_MAX_HEADS`` of the
    heads that may go together (those of one K/V head under GQA, of one
    example when a per-example bias rides along, else the whole fold) whose
    program fits the VMEM budget; one head if that alone fits the raised
    limit; 0 when not even that."""
    n = group if group > 1 else (h if biased else bh)
    need = functools.partial(_vmem_bytes, sq=sq, sk=sk, d=d,
                             itemsize=itemsize, block_q=block_q,
                             block_k=block_k)
    g = _pick_block(n, _MAX_HEADS)
    while g > 1 and need(g) > _VMEM_BUDGET:
        g = _pick_block(n, g - 1)
    return g if need(g) <= (_VMEM_BUDGET if g > 1 else _VMEM_LIMIT * 3 // 4) \
        else 0


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


def _visit(tile, g, cases, direct, looped):
    """Walk a program's ``g`` heads: ``direct(h, lo, size, mask)`` on the
    static slab of this tile index (prefix form), else ``looped(h)``."""
    def heads(body):
        jax.lax.fori_loop(0, g, lambda h, c: body(h) or c, 0)

    if cases is None:
        heads(looped)
    elif len(cases) == 1:
        heads(lambda h: direct(h, *cases[0]))
    else:
        for c, slab in enumerate(cases):
            pl.when(tile == c)(functools.partial(
                heads, lambda h, slab=slab: direct(h, *slab)))


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

def _fwd_kernel(*refs, has_bias, sm_scale, causal, block_k, prefix):
    q_ref, k_ref, v_ref = refs[:3]
    bias_ref = refs[3] if has_bias else None
    o_ref, lse_ref = refs[3 + has_bias:5 + has_bias]
    m_scr, l_scr, acc_scr = refs[5 + has_bias:]
    g, block_q, _ = q_ref.shape
    g_kv, sk, _ = k_ref.shape
    tile = pl.program_id(1)

    def part(h, q, scale, lo, size, mask):
        at, hk = _at(lo, size, block_k), h if g_kv == g else 0
        bias = None if bias_ref is None else bias_ref[0, :, at]
        s = _scores(q, k_ref[hk, at, :], bias, scale, mask, True)
        return _softmax_slab(s, v_ref[hk, at, :])

    def finish(h, m, l, acc):
        denom = jnp.where(l == 0.0, 1.0, l)            # fully-masked rows -> 0
        o_ref[h] = (acc * (1.0 / denom)).astype(o_ref.dtype)
        lse_ref[h] = _to_row(m + jnp.log(denom))

    def looped(h):
        q = _prescale(q_ref[h], sm_scale)
        m_scr[...] = jnp.full_like(m_scr, _M_FLOOR)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        _loop_k(lambda *slab: _merge(m_scr, l_scr, acc_scr,
                                     *part(h, *q, *slab)),
                tile * block_q, 0, block_q, block_k, sk // block_k, causal)
        finish(h, m_scr[:, :1], l_scr[:, :1], acc_scr[...])

    _visit(tile, g, _cases(prefix, causal, block_q, sk, False),
           lambda h, *slab: finish(
               h, *part(h, *_prescale(q_ref[h], sm_scale), *slab)), looped)


def _merge(m_scr, l_scr, acc_scr, m, l, acc):
    """Fold the softmax partials of one slab into the running ones."""
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, m)
    a, b = jnp.exp(m_prev - m_new), jnp.exp(m - m_new)[:, :1]
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * a + l * b
    acc_scr[...] = acc_scr[...] * a[:, :1] + acc * b


def _tpu_params(vmem_bytes):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_VMEM_LIMIT if vmem_bytes > _VMEM_BUDGET else None)


def _softmax_scratch(block_q, d):
    return [pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, d), jnp.float32)]        # output accumulator


def _specs(g, h, group, block_q, block_k, sq, sk, d, q_major):
    """BlockSpecs of a program of ``g`` query heads.  ``q_major``: the grid
    is (head groups, q tiles) and K/V are resident rows; else it is (head
    groups, k tiles) and Q's side is resident.  Returns the specs of a
    (bh, sq, D) q-side tensor, a (bh/group, sk, D) K/V, the (B, 1, sk) bias,
    a (bh, 1, sq) row statistic, and a per-q-head (bh, sk, D) dk/dv."""
    if group == 1:
        g_kv, kv_at = g, lambda b: b
    else:       # g divides the group: one shared K/V head per program
        g_kv, kv_at = 1, lambda b: _kv_index(b * g, h, group)
    if q_major:
        rows_q, at_q, rows_k, at_k = block_q, lambda x: x, sk, lambda x: 0
    else:
        rows_q, at_q, rows_k, at_k = sq, lambda x: 0, block_k, lambda x: x
    return (
        pl.BlockSpec((g, rows_q, d), lambda b, x, *_: (b, at_q(x), 0)),
        pl.BlockSpec((g_kv, rows_k, d),
                     lambda b, x, *_: (kv_at(b), at_k(x), 0)),
        # bias rides as (B, 1, Sk): Mosaic wants the last TWO block dims
        # divisible by (8, 128) or equal to the array's
        pl.BlockSpec((1, 1, rows_k),
                     lambda b, x, *_: (b * g // h, 0, at_k(x))),
        pl.BlockSpec((g, 1, rows_q), lambda b, x, *_: (b, 0, at_q(x))),
        pl.BlockSpec((g, rows_k, d), lambda b, x, *_: (b, at_k(x), 0)))


def _heads_per_program(q, k, h, group, bias, block_q, block_k):
    """``(heads a program, Mosaic parameters)`` of a call."""
    shape = (q.shape[1], k.shape[1], q.shape[2], q.dtype.itemsize, block_q,
             block_k)
    g = _pick_heads(q.shape[0], h, group, bias is not None, *shape)
    assert g, "flash kernels: not even one head's rows fit the VMEM limit"
    return g, _tpu_params(_vmem_bytes(g, *shape))


def _flash_fwd(q, k, v, bias, h, sm_scale, causal, block_q, block_k,
               interpret, group=1):
    """q: (B*H, S, D); k, v: (B*H//group, S, D) — GQA reads the shared K/V
    row straight from HBM via the index map, never materializing repeats;
    bias: (B, Sk) f32 or None.  Returns (out, lse)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    g, params = _heads_per_program(q, k, h, group, bias, block_q, block_k)
    qspec, kspec, bspec, row, _ = _specs(g, h, group, block_q, block_k,
                                         sq, sk, d, q_major=True)
    biased = [] if bias is None else [(bspec, bias[:, None, :])]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, has_bias=bool(biased),
                          sm_scale=sm_scale, causal=causal, block_k=block_k,
                          prefix=_prefix(causal, sq, sk, block_q, sk)),
        grid=(bh // g, sq // block_q),
        in_specs=[qspec, kspec, kspec] + [s for s, _ in biased],
        out_specs=[qspec, row],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=_softmax_scratch(block_q, d),
        compiler_params=params,
        interpret=interpret,
    )(q, k, v, *[a for _, a in biased])
    return out, lse[:, 0, :]


# --------------------------------------------------------------- backward --

def _bwd_refs(refs, has_bias, n_out):
    """(q, k, v, bias or None, do, lse, delta, outs, scratch) of a backward
    kernel."""
    bias_ref = refs[3] if has_bias else None
    rest = refs[3 + has_bias:]
    return refs[:3] + (bias_ref,) + rest[:3] + (rest[3:3 + n_out],
                                                rest[3 + n_out:])


def _dq_kernel(qoff_ref, koff_ref, *refs, has_bias, sm_scale, causal,
               block_k, prefix):
    (q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref, (dq_ref,),
     (dq_scr,)) = _bwd_refs(refs, has_bias, 1)
    g, block_q, _ = q_ref.shape
    g_kv, sk, _ = k_ref.shape
    tile = pl.program_id(1)

    def rows(h):     # what the slabs of a head share
        return (*_prescale(q_ref[h], sm_scale), do_ref[h],
                _to_dense(lse_ref[h]), _to_dense(delta_ref[h]))

    def part(h, q, scale, do, lse, delta, lo, size, mask):
        at, hk = _at(lo, size, block_k), h if g_kv == g else 0
        k = k_ref[hk, at, :]
        bias = None if bias_ref is None else bias_ref[0, :, at]
        p = jnp.exp(_scores(q, k, bias, scale, mask, True)
                    - _lanes(lse, size))                        # (bq, size)
        dp = _dot(do, v_ref[hk, at, :], _NT)                    # dO @ v^T
        ds = p * (dp - _lanes(delta, size))
        return _dot(ds.astype(k.dtype), k, _NN)                 # ds @ k

    def finish(h, dq):
        dq_ref[h] = (dq * sm_scale).astype(dq_ref.dtype)

    def looped(h):
        shared = rows(h)
        dq_scr[...] = jnp.zeros_like(dq_scr)

        def slab(*a):
            dq_scr[...] += part(h, *shared, *a)

        _loop_k(slab, qoff_ref[0] + tile * block_q, koff_ref[0], block_q,
                block_k, sk // block_k, causal)
        finish(h, dq_scr[...])

    _visit(tile, g, _cases(prefix, causal, block_q, sk, False),
           lambda h, *slab: finish(h, part(h, *rows(h), *slab)), looped)


def _dkdv_kernel(qoff_ref, koff_ref, *refs, has_bias, sm_scale, causal,
                 block_q, prefix):
    """Scores are kept transposed, (k rows, q columns): both products into
    dk and dv are then plain matmuls, and the row statistics are used as
    the (1, q) rows they are stored as."""
    (q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
     (dk_ref, dv_ref), (dk_scr, dv_scr)) = _bwd_refs(refs, has_bias, 2)
    g, sq, _ = q_ref.shape
    g_kv, block_k, _ = k_ref.shape
    tile = pl.program_id(1)

    def part(h, lo, size, mask):
        at, hk = _at(lo, size, block_q), h if g_kv == g else 0
        q, scale = _prescale(q_ref[h, at, :], sm_scale)
        do, k = do_ref[h, at, :], k_ref[hk]
        bias = None if bias_ref is None else \
            _lanes(_to_dense(bias_ref[0]), size)
        pt = jnp.exp(_scores(k, q, bias, scale, mask, False)
                     - lse_ref[h, :, at])                       # (bk, size)
        dv = _dot(pt.astype(do.dtype), do, _NN)                 # p^T @ dO
        dst = pt * (_dot(v_ref[hk], do, _NT) - delta_ref[h, :, at])
        dk = _dot(dst.astype(q.dtype), q, _NN)                  # ds^T @ q
        return (dk if scale == 1.0 else dk * sm_scale), dv      # q' carried it

    def finish(h, dk, dv):
        dk_ref[h] = dk.astype(dk_ref.dtype)
        dv_ref[h] = dv.astype(dv_ref.dtype)

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

    _visit(tile, g, _cases(prefix, causal, block_k, sq, True),
           lambda h, *slab: finish(h, *part(h, *slab)), looped)


def _offsets(q_off, k_off):
    return (jnp.asarray(q_off, jnp.int32).reshape(1),
            jnp.asarray(k_off, jnp.int32).reshape(1))


def _bwd_call(kernel, q_major, q, k, v, bias, do, lse, delta, h, sm_scale,
              causal, block_q, block_k, interpret, q_off, k_off, group):
    """One backward kernel: dq (``q_major``: a q tile against K's row) or
    dk/dv (a k tile against Q's rows).  Offsets (ring attention's traced
    block starts) place the two rows globally and force the loop form."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    g, params = _heads_per_program(q, k, h, group, bias, block_q, block_k)
    qspec, kspec, bspec, row, out = _specs(g, h, group, block_q, block_k,
                                           sq, sk, d, q_major)
    biased = [] if bias is None else [(bspec, bias[:, None, :])]
    if q_major:
        tile, other, rows, total = block_q, {"block_k": block_k}, sq, sk
        out_specs, out_shape = qspec, jax.ShapeDtypeStruct(q.shape, q.dtype)
        scratch = [(block_q, d)]
    else:
        tile, other, rows, total = block_k, {"block_q": block_q}, sk, sq
        # group > 1: per-q-head partials stay f32 so the cross-head group
        # sum keeps the kernel's f32 accumulation (cast once, after)
        out_specs, out_shape = [out, out], [
            jax.ShapeDtypeStruct((bh, sk, d),
                                 jnp.float32 if group > 1 else t.dtype)
            for t in (k, v)]
        scratch = [(block_k, d)] * 2
    prefix = q_off is None and _prefix(causal, sq, sk, tile, total)
    return pl.pallas_call(
        functools.partial(kernel, has_bias=bool(biased), sm_scale=sm_scale,
                          causal=causal, prefix=prefix, **other),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bh // g, rows // tile),
            in_specs=[qspec, kspec, kspec] + [s for s, _ in biased]
            + [qspec, row, row],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(s, jnp.float32) for s in scratch]),
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
    )(*_offsets(0 if q_off is None else q_off, 0 if k_off is None else k_off),
      q, k, v, *[a for _, a in biased],
      do, lse[:, None, :], delta[:, None, :])


def _dq_call(q, k, v, bias, do, lse, delta, h, sm_scale, causal,
             block_q, block_k, interpret, q_off=None, k_off=None, group=1):
    """dq of q against one K/V row."""
    return _bwd_call(_dq_kernel, True, q, k, v, bias, do, lse, delta, h,
                     sm_scale, causal, block_q, block_k, interpret, q_off,
                     k_off, group)


def _dkdv_call(q, k, v, bias, do, lse, delta, h, sm_scale, causal,
               block_q, block_k, interpret, q_off=None, k_off=None, group=1):
    """(dk, dv) of one K/V row from all local q rows.  Under GQA the
    outputs are PER-Q-HEAD (grid writes must not alias across the parallel
    head dimension); the caller group-sums them down to the kv heads."""
    return _bwd_call(_dkdv_kernel, False, q, k, v, bias, do, lse, delta, h,
                     sm_scale, causal, block_q, block_k, interpret, q_off,
                     k_off, group)


def _flash_bwd(q, k, v, bias, out, lse, do, h, sm_scale, causal,
               block_q, block_k, interpret, group=1):
    # delta_r = rowsum(dO * O): tiny elementwise+reduce, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    dq = _dq_call(q, k, v, bias, do, lse, delta, h, sm_scale, causal,
                  block_q, block_k, interpret, group=group)
    dk, dv = _dkdv_call(q, k, v, bias, do, lse, delta, h, sm_scale, causal,
                        block_q, block_k, interpret, group=group)
    if group > 1:   # per-q-head contributions -> sum each kv-head group
        bh, sk, d = dk.shape
        b = bh // h
        dk = dk.reshape(b, h // group, group, sk, d).sum(2)
        dv = dv.reshape(b, h // group, group, sk, d).sum(2)
        dk = dk.reshape(b * (h // group), sk, d).astype(k.dtype)
        dv = dv.reshape(b * (h // group), sk, d).astype(v.dtype)
    return dq, dk, dv


# ------------------------------------------------- ring-attention carry op --

def _block_update_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref,
                         m_in_ref, l_in_ref, o_in_ref,
                         m_out_ref, l_out_ref, o_out_ref,
                         m_scr, l_scr, acc_scr, *, sm_scale, causal, block_k):
    """One ring-attention step: fold a remote K/V block into the running
    (m, l, o) online-softmax carry.  The forward kernel's loop form, but the
    accumulator state enters and leaves through HBM (it is a lax.scan carry
    in ``parallel/ring_attention.py``), and causal masking is over GLOBAL
    positions (q_off / k_off scalars = ring block starts)."""
    g, block_q, _ = q_ref.shape
    tile = pl.program_id(1)

    def looped(h):
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

    _visit(tile, g, None, None, looped)


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
    shape = (sq, sk, d, q.dtype.itemsize, bq, bk)
    g = bq and bk and _pick_heads(bh, bh, 1, False, *shape)
    if not g:
        return None
    qspec, kspec, _, row, _ = _specs(g, bh, 1, bq, bk, sq, sk, d,
                                     q_major=True)
    m2, l2, o2 = pl.pallas_call(
        functools.partial(_block_update_kernel, sm_scale=float(sm_scale),
                          causal=bool(causal), block_k=bk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bh // g, sq // bq),
            in_specs=[qspec, kspec, kspec, row, row, qspec],
            out_specs=[row, row, qspec],
            scratch_shapes=_softmax_scratch(bq, d)),
        out_shape=[
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        ],
        compiler_params=_tpu_params(_vmem_bytes(g, *shape)),
        interpret=interpret,
    )(*_offsets(q_off, k_off), q, k, v, m[:, None, :], l[:, None, :],
      o.astype(jnp.float32))
    return m2[:, 0, :], l2[:, 0, :], o2


# ------------------------------------------------------------- public API --

@functools.lru_cache(maxsize=64)
def _make_flash(h, sm_scale, causal, block_q, block_k, interpret, group=1):
    @jax.custom_vjp
    def attend(q, k, v, bias):
        out, _ = _flash_fwd(q, k, v, bias, h, sm_scale, causal,
                            block_q, block_k, interpret, group=group)
        return out

    def fwd(q, k, v, bias):
        out, lse = _flash_fwd(q, k, v, bias, h, sm_scale, causal,
                              block_q, block_k, interpret, group=group)
        return out, (q, k, v, bias, out, lse)

    def bwd(res, do):
        q, k, v, bias, out, lse = res
        dq, dk, dv = _flash_bwd(q, k, v, bias, out, lse, do, h, sm_scale,
                                causal, block_q, block_k, interpret,
                                group=group)
        return dq, dk, dv, None if bias is None else jnp.zeros_like(bias)

    attend.defvjp(fwd, bwd)
    return attend


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
    shrink to divisors of S, and the heads a program takes follow from the
    shapes (``_pick_heads``).
    """
    if interpret is None:
        interpret = not _on_tpu()
    b, sq, h, d = q.shape
    sk = k.shape[1]
    h_kv = k.shape[2]
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
    g = bq and bk and _pick_heads(b * h, h, group, kv_mask is not None, sq,
                                  sk, d, q.dtype.itemsize, bq, bk)
    if not g:
        logging.warning_once(
            "flash_attention q%s k%s: no %d-aligned block divides the "
            "sequence, or one head's rows pass the VMEM budget; running XLA "
            "attention at this site", tuple(q.shape), tuple(k.shape), align)
        if group > 1:
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        return _xla_attention(q, k, v, causal, kv_mask, sm_scale)
    logging.info_once(
        "flash_attention q%s k%s %s: %d heads a program, block_q %d, "
        "block_k %d, %s, %d bytes of VMEM reckoned",
        tuple(q.shape), tuple(k.shape), str(q.dtype), g, bq, bk,
        "the visible prefix in one slab" if _prefix(causal, sq, sk, bq, sk)
        else "at most %d k tiles a q tile in a loop" % (sk // bk),
        _vmem_bytes(g, sq, sk, d, q.dtype.itemsize, bq, bk))
    bias = None if kv_mask is None else \
        jnp.where(kv_mask, 0.0, _NEG_INF).astype(jnp.float32)

    def fold(t):      # (B, S, H', D) -> (B*H', S, D)
        return t.transpose(0, 2, 1, 3).reshape(b * t.shape[2], t.shape[1], d)

    attend = _make_flash(h, float(sm_scale), bool(causal), bq, bk,
                         bool(interpret), group)
    out = attend(fold(q), fold(k), fold(v), bias)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
