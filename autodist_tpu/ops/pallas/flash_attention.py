"""Pallas TPU flash attention (tiled online-softmax) with a custom VJP.

The XLA attention path (``jax.nn.dot_product_attention``) materializes the
(S, S) score matrix in HBM — O(S^2) memory traffic that caps context
length and starves the MXU at long S.  This kernel is the standard
flash-attention recipe laid out for the TPU memory hierarchy:

  * grid over (batch*heads, q-blocks, k-blocks) with the k dimension
    innermost ("arbitrary" semantics) so VMEM scratch carries the running
    max / denominator / output accumulator across k-blocks — scores never
    leave VMEM;
  * both matmuls per block hit the MXU with f32 accumulation
    (``preferred_element_type``) over bf16 operands;
  * causal masking over block-local iotas, with fully-masked k-blocks
    skipped via ``pl.when`` (upper-triangular compute never runs); key
    padding masks (the BERT case) ride a per-key additive bias row;
  * backward = two kernels (dkdv with q innermost, dq with k innermost)
    that recompute p from the saved logsumexp instead of stashing the
    (S, S) probability matrix — the flash-attention memory contract.

Reference parity note: the reference (petuum/autodist) has no attention
kernels at all (its models ride stock TF layers); this is part of the
"exceeds" long-context surface (SURVEY.md section 5) next to
``parallel/ring_attention.py``, which streams K/V blocks *between* chips
while this kernel tiles *within* a chip.

Kernel playbook: /opt/skills/guides/pallas_guide.md (grid/BlockSpec,
scratch persistence across the innermost grid dim, MXU
preferred_element_type, 2D iota, ``pl.when`` predication).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from autodist_tpu.utils import logging

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
_NEG_INF = -1e30  # finite: -inf NaNs under (0 * -inf) in masked-row algebra
# running-max floor: keeps exp(masked - m) == 0 when a whole block (or row)
# is masked out, so fully-padded rows produce exact zeros fwd AND bwd
_M_FLOOR = -1e20
_LANES = 128      # broadcast width for the m/l scratch rows


def _pick_block(s, want, multiple=1):
    """Largest divisor of ``s`` that is <= want (and a multiple of
    ``multiple``); 0 when no such divisor exists."""
    b = min(want, s)
    b -= b % multiple
    while b >= multiple and s % b:
        b -= multiple
    return b if b >= multiple else 0


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


def _scores(q_ref, k_ref, bias_ref, i, j, *, sm_scale, causal,
            block_q, block_k, q_off=0, k_off=0):
    """Masked f32 score block (bq, bk); shared by the fwd, ring-update and
    both bwd kernels so recomputation matches the forward bit-for-bit.
    ``q_off``/``k_off`` shift the causal mask to GLOBAL positions (the
    ring-attention case); ``bias_ref=None`` skips the key-padding bias."""
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    if bias_ref is not None:
        # bias rides as (B, 1, Sk) with (1, 1, block_k) blocks — Mosaic
        # requires the last TWO block dims divisible by (8, 128) or equal
        # to the array dims, which a 2-D (1, block_k) block violates
        s = s + bias_ref[0, 0][None, :]
    if causal:
        rows = q_off + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = k_off + j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    return s


def _online_update(s, v_ref, m_scr, l_scr, acc_scr):
    """One online-softmax accumulation step over a score block — the single
    shared implementation for the fwd kernel and the ring block-update
    kernel (bit-exactness between them is asserted in the dryrun)."""
    m_prev = m_scr[:, :1]                          # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                         # (bq, bk)
    corr = jnp.exp(m_prev - m_new)                 # (bq, 1)
    l_scr[:] = jnp.broadcast_to(
        l_scr[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True),
        l_scr.shape)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    pv = jax.lax.dot_general(                      # (bq, D) f32
        p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc_scr[:] = acc_scr[:] * corr + pv


# ---------------------------------------------------------------- forward --

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, sm_scale, causal, block_q, block_k, num_k):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _M_FLOOR)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: skip k-blocks that start past the last query row of this block
    visible = (i + 1) * block_q - 1 >= j * block_k
    should_compute = (not causal) or visible

    @pl.when(should_compute)
    def _():
        s = _scores(q_ref, k_ref, bias_ref, i, j, sm_scale=sm_scale,
                    causal=causal, block_q=block_q, block_k=block_k)
        _online_update(s, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(j == num_k - 1)
    def _():
        l = l_scr[:, :1]
        denom = jnp.where(l == 0.0, 1.0, l)            # fully-masked rows -> 0
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(denom)
        lse_ref[0, 0] = lse[:, 0]


def _fwd_scratch(block_q, d):
    from jax.experimental.pallas import tpu as pltpu
    return [
        pltpu.VMEM((block_q, _LANES), jnp.float32),   # running max
        pltpu.VMEM((block_q, _LANES), jnp.float32),   # running denominator
        pltpu.VMEM((block_q, d), jnp.float32),        # output accumulator
    ]


def _tpu_params(dimension_semantics):
    from jax.experimental.pallas import tpu as pltpu
    try:
        return pltpu.CompilerParams(dimension_semantics=dimension_semantics)
    except (TypeError, AttributeError):  # older jax spelling
        return pltpu.TPUCompilerParams(dimension_semantics=dimension_semantics)


def _flash_fwd(q, k, v, bias, h, sm_scale, causal, block_q, block_k,
               interpret, group=1):
    """q: (B*H, S, D); k, v: (B*H//group, S, D) — GQA reads the shared K/V
    block straight from HBM via the index map, never materializing repeats;
    bias: (B, Sk) f32.  Returns (out, lse)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    kern = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k=nk)
    out, lse = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (_kv_index(b, h, group), j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j: (_kv_index(b, h, group), j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b // h, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=_fwd_scratch(block_q, d),
        compiler_params=_tpu_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, bias[:, None, :])
    return out, lse[:, 0, :]


# --------------------------------------------------------------- backward --

def _dkdv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                 lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                 *, sm_scale, causal, block_q, block_k, num_q):
    j, i = pl.program_id(1), pl.program_id(2)      # k-block outer, q inner
    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    last_q = q_off + (i + 1) * block_q - 1
    should_compute = jnp.logical_or(not causal, last_q >= k_off + j * block_k)

    @pl.when(should_compute)
    def _():
        s = _scores(q_ref, k_ref, bias_ref, i, j, sm_scale=sm_scale,
                    causal=causal, block_q=block_q, block_k=block_k,
                    q_off=q_off, k_off=k_off)
        p = jnp.exp(s - lse_ref[0, 0][:, None])        # (bq, bk)
        do = do_ref[0].astype(jnp.float32)             # (bq, D)
        dv_scr[:] += jax.lax.dot_general(              # p^T @ dO -> (bk, D)
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(                      # dO @ v^T -> (bq, bk)
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * sm_scale
        dk_scr[:] += jax.lax.dot_general(              # ds^T @ q -> (bk, D)
            ds, q_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == num_q - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
               lse_ref, delta_ref, dq_ref, dq_scr,
               *, sm_scale, causal, block_q, block_k, num_k):
    i, j = pl.program_id(1), pl.program_id(2)      # q-block outer, k inner
    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    last_q = q_off + (i + 1) * block_q - 1
    should_compute = jnp.logical_or(not causal, last_q >= k_off + j * block_k)

    @pl.when(should_compute)
    def _():
        s = _scores(q_ref, k_ref, bias_ref, i, j, sm_scale=sm_scale,
                    causal=causal, block_q=block_q, block_k=block_k,
                    q_off=q_off, k_off=k_off)
        p = jnp.exp(s - lse_ref[0, 0][:, None])
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, None]) * sm_scale
        dq_scr[:] += jax.lax.dot_general(              # ds @ k -> (bq, D)
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == num_k - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _offsets(q_off, k_off):
    return (jnp.asarray(q_off, jnp.int32).reshape(1),
            jnp.asarray(k_off, jnp.int32).reshape(1))


def _dq_call(q, k, v, bias, do, lse, delta, h, sm_scale, causal,
             block_q, block_k, interpret, q_off=0, k_off=0, group=1):
    """dq for one (q, k-block) pair; offsets place the blocks globally."""
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    qspec = pl.BlockSpec((1, block_q, d), lambda b, x, y, *_: (b, x, 0))
    row = pl.BlockSpec((1, 1, block_q), lambda b, x, y, *_: (b, 0, x))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq, nk),
        in_specs=[
            qspec,
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, *_: (_kv_index(b, h, group), j, 0)),
            pl.BlockSpec((1, block_k, d),
                         lambda b, i, j, *_: (_kv_index(b, h, group), j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, i, j, *_: (b // h, 0, j)),
            qspec, row, row,
        ],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )
    qo, ko = _offsets(q_off, k_off)
    return pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k=nk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        compiler_params=_tpu_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qo, ko, q, k, v, bias[:, None, :], do, lse[:, None, :],
      delta[:, None, :])


def _dkdv_call(q, k, v, bias, do, lse, delta, h, sm_scale, causal,
               block_q, block_k, interpret, q_off=0, k_off=0, group=1):
    """(dk, dv) for one k-block from all local q blocks.  Under GQA the
    outputs are PER-Q-HEAD (grid writes must not alias across the parallel
    b dimension); the caller group-sums them down to the kv heads."""
    from jax.experimental.pallas import tpu as pltpu

    bh, sq, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    # k-block outer, q-block inner: grid indices are (b, j, i)
    qspec_i = pl.BlockSpec((1, block_q, d), lambda b, j, i, *_: (b, i, 0))
    row_i = pl.BlockSpec((1, 1, block_q), lambda b, j, i, *_: (b, 0, i))
    kspec_in = pl.BlockSpec((1, block_k, d),
                            lambda b, j, i, *_: (_kv_index(b, h, group), j, 0))
    kspec_out = pl.BlockSpec((1, block_k, d), lambda b, j, i, *_: (b, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nk, nq),
        in_specs=[qspec_i, kspec_in, kspec_in,
                  pl.BlockSpec((1, 1, block_k),
                               lambda b, j, i, *_: (b // h, 0, j)),
                  qspec_i, row_i, row_i],
        out_specs=[kspec_out, kspec_out],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
    )
    qo, ko = _offsets(q_off, k_off)
    return pl.pallas_call(
        functools.partial(_dkdv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=nq),
        grid_spec=grid_spec,
        # group > 1: per-q-head partials stay f32 so the cross-head group
        # sum keeps the kernel's f32 accumulation (cast once, after)
        out_shape=[jax.ShapeDtypeStruct(
                       (bh, sk, d), jnp.float32 if group > 1 else k.dtype),
                   jax.ShapeDtypeStruct(
                       (bh, sk, d), jnp.float32 if group > 1 else v.dtype)],
        compiler_params=_tpu_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qo, ko, q, k, v, bias[:, None, :], do, lse[:, None, :],
      delta[:, None, :])


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
                         m_scr, l_scr, acc_scr,
                         *, sm_scale, causal, block_q, block_k, num_k):
    """One ring-attention step: fold a remote K/V block into the running
    (m, l, o) online-softmax carry.  Same tiling as the fwd kernel, but the
    accumulator state enters and leaves through HBM (it is a lax.scan carry
    in ``parallel/ring_attention.py``), and causal masking is over GLOBAL
    positions (q_off / k_off scalars = ring block starts)."""
    i, j = pl.program_id(1), pl.program_id(2)
    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(j == 0)
    def _():
        # clamp at the floor: the XLA ring path seeds m with -inf, under
        # which exp(m_prev - m_new) would NaN at the first real block
        m_scr[:] = jnp.broadcast_to(
            jnp.maximum(m_in_ref[0, 0][:, None], _M_FLOOR), m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_in_ref[0, 0][:, None], l_scr.shape)
        acc_scr[:] = o_in_ref[0].astype(jnp.float32)

    last_q = q_off + (i + 1) * block_q - 1
    should_compute = jnp.logical_or(not causal, last_q >= k_off + j * block_k)

    @pl.when(should_compute)
    def _():
        s = _scores(q_ref, k_ref, None, i, j, sm_scale=sm_scale,
                    causal=causal, block_q=block_q, block_k=block_k,
                    q_off=q_off, k_off=k_off)
        _online_update(s, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(j == num_k - 1)
    def _():
        m_out_ref[0, 0] = m_scr[:, 0]
        l_out_ref[0, 0] = l_scr[:, 0]
        o_out_ref[0] = acc_scr[:]


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
    align = 1 if interpret else 128
    bq = _pick_block(sq, block_q, align)
    bk = _pick_block(sk, block_k, align)
    if not bq or not bk:
        return None
    nq, nk = sq // bq, sk // bk
    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j, *_: (b, j, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j, *_: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j, *_: (b, 0, i)),
            pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq), lambda b, i, j, *_: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j, *_: (b, 0, i)),
            pl.BlockSpec((1, bq, d), lambda b, i, j, *_: (b, i, 0)),
        ],
        scratch_shapes=_fwd_scratch(bq, d),
    )
    kern = functools.partial(
        _block_update_kernel, sm_scale=float(sm_scale), causal=bool(causal),
        block_q=bq, block_k=bk, num_k=nk)
    qo = jnp.asarray(q_off, jnp.int32).reshape(1)
    ko = jnp.asarray(k_off, jnp.int32).reshape(1)
    m2, l2, o2 = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
            jax.ShapeDtypeStruct((bh, sq, d), jnp.float32),
        ],
        compiler_params=_tpu_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qo, ko, q, k, v, m[:, None, :], l[:, None, :], o.astype(jnp.float32))
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
        return dq, dk, dv, jnp.zeros_like(bias)

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
    (the tests' CPU path).  Block sizes shrink to divisors of S.
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
    align = 1 if interpret else 128
    bq = _pick_block(sq, block_q, align)
    bk = _pick_block(sk, block_k, align)
    if not bq or not bk:
        logging.warning_once(
            "flash_attention q%s k%s: no %d-aligned block divides the "
            "sequence; running XLA attention at this site",
            tuple(q.shape), tuple(k.shape), align)
        if group > 1:
            k = jnp.repeat(k, group, axis=2)
            v = jnp.repeat(v, group, axis=2)
        return _xla_attention(q, k, v, causal, kv_mask, sm_scale)
    if kv_mask is None:
        bias = jnp.zeros((b, sk), jnp.float32)
    else:
        bias = jnp.where(kv_mask, 0.0, _NEG_INF).astype(jnp.float32)

    def fold(t):      # (B, S, H', D) -> (B*H', S, D)
        return t.transpose(0, 2, 1, 3).reshape(b * t.shape[2], t.shape[1], d)

    attend = _make_flash(h, float(sm_scale), bool(causal), bq, bk,
                         bool(interpret), group)
    out = attend(fold(q), fold(k), fold(v), bias)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
