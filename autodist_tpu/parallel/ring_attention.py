"""Ring attention: sequence/context parallelism over a mesh axis.

First-class long-context support (absent from the reference — SURVEY.md
section 5 — but required of this framework): the sequence dimension is
sharded over a mesh axis; each device holds a query block and streams
key/value blocks around the ring with ``ppermute`` while accumulating a
numerically-stable online softmax (flash-attention style running max /
denominator).  Peak memory is O(S/R) per device and the K/V transfers ride
ICI neighbor links, overlapping with the block matmuls (XLA schedules the
ppermute concurrently with compute).

Also provides :func:`all_to_all_attention` ("Ulysses"-style): for models
with many heads, an ``all_to_all`` re-shards sequence -> heads so each
device computes full-sequence attention for a head subset — fewer, larger
MXU matmuls at the cost of two all_to_alls.

All functions run inside ``shard_map`` with the sequence axis sharded.
"""
import functools

import jax
import jax.numpy as jnp

from autodist_tpu.kernel.collectives import ppermute, ring_perm
from autodist_tpu.utils import logging


def _online_block(q, k_blk, v_blk, bias_blk, m, l, o, scale):
    """One flash-style block update.  q:(B,Sq,H,D) k/v:(B,Sk,H,D),
    m/l:(B,H,Sq), o:(B,Sq,H,D)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k_blk) * scale
    if bias_blk is not None:
        s = s + bias_blk
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhqk,bkhd->bqhd", p, v_blk)
    return m_new, l_new, o_new


@functools.lru_cache(maxsize=32)
def _make_ring_flash(axis_name, causal, b, h, sq, d, bq, bk, scale,
                     interpret):
    """Ring attention with the Pallas flash kernels doing the per-step block
    math: fwd folds each visiting K/V block into the (m, l, o) carry via
    ``flash_block_update`` (scores never leave VMEM); bwd is a second ring
    pass — each device adds its local (dk, dv) contribution to the visiting
    block's gradient, which travels the ring WITH the block and arrives home
    fully summed after R hops, while dq accumulates locally.  Everything is
    position-offset-aware so the causal mask is over GLOBAL positions."""
    from autodist_tpu.ops.pallas import flash_attention as F

    bh = b * h

    def _ring(body, carry, r):
        return jax.lax.scan(body, carry, jnp.arange(r))

    @jax.custom_vjp
    def attend(qf, kf, vf):
        out, _ = _fwd(qf, kf, vf)
        return out

    def _fwd(qf, kf, vf):
        r = jax.lax.axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        q_off = idx * sq
        perm = ring_perm(r)
        m0 = jnp.full((bh, sq), F._M_FLOOR, jnp.float32)
        l0 = jnp.zeros((bh, sq), jnp.float32)
        o0 = jnp.zeros((bh, sq, d), jnp.float32)
        m0, l0, o0 = _pcast_varying((m0, l0, o0), axis_name)

        def body(carry, step):
            k_blk, v_blk, m, l, o = carry
            blk = jnp.mod(idx - step, r)
            m, l, o = F.flash_block_update(
                qf, k_blk, v_blk, m, l, o, q_off, blk * sq, causal=causal,
                sm_scale=scale, block_q=bq, block_k=bk, interpret=interpret)
            k_blk = ppermute(k_blk, axis_name, perm)
            v_blk = ppermute(v_blk, axis_name, perm)
            return (k_blk, v_blk, m, l, o), None

        (kf, vf, m, l, o), _ = _ring(body, (kf, vf, m0, l0, o0), r)
        denom = jnp.where(l == 0.0, 1.0, l)
        out = (o / denom[..., None]).astype(qf.dtype)
        lse = m + jnp.log(denom)
        return out, lse

    def fwd(qf, kf, vf):
        out, lse = _fwd(qf, kf, vf)
        return out, (qf, kf, vf, out, lse)

    def bwd(res, do):
        qf, kf, vf, out, lse = res
        r = jax.lax.axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        q_off = idx * sq
        perm = ring_perm(r)
        args = dict(sm_scale=scale, causal=causal, block_q=bq, block_k=bk,
                    interpret=interpret)

        def body(carry, step):
            k_blk, v_blk, dk, dv, dq = carry
            blk = jnp.mod(idx - step, r)
            k_off = blk * sq
            dq_p = F._dq_call(qf, k_blk, v_blk, None, do, out, lse, h,
                              q_off=q_off, k_off=k_off, **args)
            dk_p, dv_p = F._dkdv_call(qf, k_blk, v_blk, None, do, out, lse,
                                      h, q_off=q_off, k_off=k_off, **args)
            dq = dq + dq_p.astype(jnp.float32)
            dk = dk + dk_p.astype(jnp.float32)
            dv = dv + dv_p.astype(jnp.float32)
            # gradients travel the ring WITH their K/V block
            k_blk, v_blk, dk, dv = (ppermute(t, axis_name, perm)
                                    for t in (k_blk, v_blk, dk, dv))
            return (k_blk, v_blk, dk, dv, dq), None

        z = jnp.zeros((bh, sq, d), jnp.float32)
        z = _pcast_varying(z, axis_name)
        (_, _, dk, dv, dq), _ = _ring(body, (kf, vf, z, z, z), r)
        return (dq.astype(qf.dtype), dk.astype(kf.dtype),
                dv.astype(vf.dtype))

    attend.defvjp(fwd, bwd)
    return attend


def _pcast_varying(tree, axis_name):
    """Mark constants as device-varying over ``axis_name`` so scan carries
    that mix them with ppermute'd blocks type-check under shard_map's
    default varying-manual-axes (VMA) validation.  No-op where the API or
    the context (no manual axes) doesn't apply."""
    pcast = getattr(jax.lax, "pcast", None)
    if pcast is None:
        return tree
    try:
        return jax.tree.map(
            lambda t: pcast(t, (axis_name,), to="varying"), tree)
    except Exception:
        return tree


def _ring_flash(q, k, v, axis_name, causal):
    """Flash-kernel ring path; None when the shapes cannot be tiled (caller
    falls back to the XLA block update)."""
    from autodist_tpu.ops.pallas import flash_attention as F

    interpret = not F._on_tpu()
    B, Sq, H, D = q.shape
    align = 1 if interpret else 128
    bq = F._pick_block(Sq, F.DEFAULT_BLOCK_Q, align)
    bk = F._pick_block(Sq, F.DEFAULT_BLOCK_K, align)
    if not bq or not bk:
        return None
    scale = 1.0 / (D ** 0.5)

    def fold(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], D)

    attend = _make_ring_flash(axis_name, bool(causal), B, H, Sq, D, bq, bk,
                              float(scale), interpret)
    out = attend(fold(q), fold(k), fold(v))
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


def ring_attention(q, k, v, axis_name, causal=False, impl="auto"):
    """Blockwise ring attention.

    Args:
      q, k, v: local blocks (B, S_local, H, D) — the sequence dim is sharded
        over `axis_name` (device i holds positions [i*S_local, (i+1)*S_local)).
      causal: apply a causal mask over *global* positions.
      impl: "auto" (flash kernels on TPU, XLA elsewhere) | "flash" | "xla" —
        the per-step block math; the ring schedule is identical.

    Returns the local attention output block (B, S_local, H, D).
    """
    from autodist_tpu.ops.pallas.flash_attention import use_flash

    if use_flash(impl):
        out = _ring_flash(q, k, v, axis_name, causal)
        if out is not None:
            return out
        logging.warning_once(
            "ring_attention q%s over '%s': the local sequence cannot be "
            "tiled for the flash block update; running the XLA block "
            "update at this site", tuple(q.shape), str(axis_name))
    R = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, Sq, H, D = q.shape
    scale = 1.0 / jnp.sqrt(D).astype(q.dtype)
    q_pos = idx * Sq + jnp.arange(Sq)

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    o0 = jnp.zeros((B, Sq, H, D), jnp.float32)
    # the accumulators START as unvarying constants but the scan body folds
    # device-varying blocks into them; under shard_map's default VMA check
    # the carry types must agree, so mark them varying up front (engine
    # paths run check_vma=False and never see this, bare shard_map users do)
    m0, l0, o0 = _pcast_varying((m0, l0, o0), axis_name)
    perm = ring_perm(R)

    def body(carry, step):
        k_blk, v_blk, m, l, o = carry
        # device `idx` holds block (idx - step) mod R at this step
        blk = jnp.mod(idx - step, R)
        bias = None
        if causal:
            k_pos = blk * Sq + jnp.arange(Sq)
            mask = q_pos[:, None] >= k_pos[None, :]          # (Sq, Sk)
            bias = jnp.where(mask, 0.0, -jnp.inf)[None, None]
        m, l, o = _online_block(q.astype(jnp.float32), k_blk.astype(jnp.float32),
                                v_blk.astype(jnp.float32), bias, m, l, o, scale)
        k_blk = ppermute(k_blk, axis_name, perm)
        v_blk = ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, m, l, o), None

    (k, v, m, l, o), _ = jax.lax.scan(body, (k, v, m0, l0, o0),
                                      jnp.arange(R))
    # rows with no visible keys (fully masked) have l == 0; output 0 there
    denom = jnp.where(l == 0.0, 1.0, l)
    out = o / denom.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def all_to_all_attention(q, k, v, axis_name, causal=False):
    """Ulysses-style sequence parallelism: all_to_all swaps the sharded dim
    from sequence to heads, each device runs full-sequence attention on its
    head subset, then the inverse all_to_all restores sequence sharding.
    Requires num_heads % axis_size == 0."""
    R = jax.lax.axis_size(axis_name)
    B, Sl, H, D = q.shape
    if H % R != 0:
        raise ValueError(f"num_heads {H} must divide by axis size {R}")

    def seq_to_heads(x):
        # (B, Sl, H, D) -> (B, Sl*R, H/R, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    S = qg.shape[1]
    bias = None
    if causal:
        pos = jnp.arange(S)
        bias = jnp.where(pos[:, None] >= pos[None, :], 0.0, -jnp.inf)[None, None]
    out = jax.nn.dot_product_attention(qg, kg, vg, bias=bias)
    return heads_to_seq(out)
