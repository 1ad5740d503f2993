"""The gated delta rule of a gated-DeltaNet layer, in its chunked form.

Per value head, with a state ``S`` of ``[d_k, d_v]`` that starts at zero, the
rule reads position ``t``'s ``q_t, k_t`` (``[d_k]``), ``v_t`` (``[d_v]``), a
log decay ``g_t <= 0`` and a write strength ``beta_t``::

    S' = exp(g_t) * S_{t-1}
    u_t = beta_t * (v_t - S'^T k_t)
    S_t = S' + k_t u_t^T
    o_t = S_t^T q_t

A scan over positions is the definition (the tests' reference); trained at
thousands of positions it is one small matrix-vector product after another.
Here a chunk of ``C`` positions is computed as matrix products.  With ``G``
the running sum of ``g`` inside the chunk and ``S_0`` the state the chunk
starts from, the ``u`` of the chunk solve a unit lower triangular system::

    (I + A) U = beta * (V - exp(G) * (K S_0)),
    A_ij = beta_i * exp(G_i - G_j) * (k_i . k_j)   for j < i, else 0

so with ``T = (I + A)^-1``, ``W = T (beta * exp(G) * K)`` and
``U_0 = T (beta * V)``::

    U   = U_0 - W S_0
    O   = (exp(G) * Q) S_0 + tril((Q K^T) * exp(G_i - G_j)) U
    S_C = exp(G_C) * S_0 + (exp(G_C - G) * K)^T U

Every exponent is a difference ``G_i - G_j`` with ``j <= i`` or a ``G_i``
itself, so none is positive and no ``exp`` overflows.  Everything that does
not need ``S_0`` is computed for a block of chunks at once; a ``lax.scan`` over
the chunks carries the state in float32 and does four small products a step.
The matrix products take their operands in ``dtype`` (bfloat16 in training)
and accumulate in float32; the decays, the triangular inverse and the
carried state are float32 whatever ``dtype`` is.

That scan form is the definition the tests hold to the recurrence, and what
runs on a CPU and at head widths a TPU kernel does not tile.  On a TPU, at
heads whose width is a multiple of 128 and chunks of 64,
``chunk_gated_delta_rule`` hands the same arguments to the Pallas kernels of
``ops/pallas/gated_delta.py``: the same equations with a chunk's operands,
the inverse and the state in VMEM, forward and backward (the tests run them
in the Pallas interpreter against the same recurrence).  Nothing selects the
path but the backend and the shapes.
"""
import math

import jax
import jax.numpy as jnp

from autodist_tpu.ops.pallas import flash_attention
from autodist_tpu.ops.pallas import gated_delta as kernels

# float32 products as three bfloat16 passes: 2**-16 a product, where one
# pass (the TPU's default for float32) would leave the inverse at 2**-8
_F32 = jax.lax.Precision.HIGH


def _mm(a, b, spec, dtype):
    """``einsum`` with operands in ``dtype`` and a float32 result."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


@jax.custom_vjp
def inv_unit_lower(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` of ``[..., C, C]``,
    float32.  ``a`` is nilpotent, so with ``x = -a`` the inverse is the
    finite sum ``I + x + ... + x^(C-1) = (I + x)(I + x^2)(I + x^4)...``:
    ``2 (ceil(log2 C) - 1)`` products of C x C matrices (float32 to 2**-16
    each: ``Precision.HIGH``) and no sequential substitution.  The backward pass is ``-T^T dT T^T`` and keeps only T."""
    c = a.shape[-1]
    x = -a
    t = x + jnp.eye(c, dtype=a.dtype)
    for _ in range(max(0, math.ceil(math.log2(max(c, 2))) - 1)):
        x = jnp.matmul(x, x, precision=_F32)
        t = t + jnp.matmul(t, x, precision=_F32)
    return t


def _inv_fwd(a):
    t = inv_unit_lower(a)
    return t, t


def _inv_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(jnp.matmul(tt, dt, precision=_F32), tt,
                     precision=_F32)
    return (jnp.tril(da, -1),)


inv_unit_lower.defvjp(_inv_fwd, _inv_bwd)


# Chunks whose state-free parts are computed at once.  A block is a
# ``jax.checkpoint``: the backward pass keeps the state at each block's start
# and computes a block's intermediates again, so what is live is one block's
# and not the sequence's (at 8,192 positions, 4 sequences and 32 heads the
# states alone are 1 GB, and the deviceless compile of the benchmark's step
# read 14.1 GB of temporaries without the blocks against 9.2 GB with them).
CHUNKS_PER_BLOCK = 16


def chunk_gated_delta_rule(q, k, v, g, beta, chunk_size=64, dtype=None):
    """The rule over whole sequences, from a zero state.

    Args:
      q, k: ``[B, S, H_k, d_k]``, already normalised and scaled by the caller.
      v: ``[B, S, H_v, d_v]`` with ``H_v`` a multiple of ``H_k``; key head
        ``h`` serves the value heads ``h * (H_v // H_k) ...``.
      g: ``[B, S, H_v]`` log decays, ``<= 0``; beta: ``[B, S, H_v]``.
      chunk_size: positions a chunk; the sequence is padded to whole blocks
        with positions that write nothing (``beta = 0``) and do not decay.
      dtype: the matrix products' operand type; default ``v.dtype``.

    Returns ``o`` of ``[B, S, H_v, d_v]`` in ``dtype``.
    """
    # the kernels on a TPU (asked as the flash kernels ask, so that a
    # deviceless compile for a TPU takes the same path) where they tile
    # the heads and the chunk inside VMEM; this scan form everywhere else
    dtype = jnp.dtype(dtype or v.dtype)
    if flash_attention._on_tpu() and kernels.tiles(
            chunk_size, q.shape[-1], v.shape[-1], v.shape[2] // q.shape[2],
            dtype.itemsize):
        return kernels.gated_delta_rule(q, k, v, g, beta, chunk_size, dtype)
    b, s, h_v, _ = v.shape
    rep = h_v // q.shape[2]
    if rep * q.shape[2] != h_v:
        raise ValueError(f"{h_v} value heads over {q.shape[2]} key heads")
    c = min(chunk_size, s)
    span = min(CHUNKS_PER_BLOCK, -(-s // c))
    nb = -(-s // (c * span))

    def blocks(x):
        """``[B, S, H, ...]`` -> ``[NB, span, B, H, C, ...]``, zero-padded."""
        x = jnp.pad(x, [(0, 0), (0, nb * span * c - s)]
                    + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, nb, span, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 0, 2), 4, 3)

    lower = jnp.tril(jnp.ones((c, c), bool))

    def chunk_step(state, x):
        w_c, u0_c, qg_c, p_c, kd_c, decay_c = x
        u = (u0_c - _mm(w_c, state, "...id,...de->...ie", dtype)
             ).astype(dtype)
        o = _mm(qg_c, state, "...id,...de->...ie", dtype) \
            + _mm(p_c, u, "...ij,...je->...ie", dtype)
        state = decay_c * state + _mm(kd_c, u, "...id,...ie->...de", dtype)
        return state, o.astype(dtype)

    @jax.checkpoint
    def block_step(state, x):
        q, k, v, g, beta = x             # [span, B, H(_k), C, ...]
        if rep > 1:
            q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
        g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
        cum = jnp.cumsum(g[..., 0], axis=-1)                 # [span,B,H,C]
        last = cum[..., -1:]
        decay = jnp.exp(jnp.where(
            lower, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        up = jnp.exp(cum)[..., None]                         # exp(G_i)
        kf = k.astype(jnp.float32)
        a = jnp.tril(beta * _mm(k, k, "...id,...jd->...ij", dtype) * decay,
                     -1)
        t = inv_unit_lower(a)
        w = _mm(t, beta * up * kf, "...ij,...jd->...id", dtype).astype(dtype)
        u0 = _mm(t, beta * v.astype(jnp.float32), "...ij,...jd->...id",
                 dtype)
        qg = (up * q.astype(jnp.float32)).astype(dtype)
        p = (_mm(q, k, "...id,...jd->...ij", dtype) * decay).astype(dtype)
        kd = (jnp.exp(last - cum)[..., None] * kf).astype(dtype)
        return jax.lax.scan(chunk_step, state,
                            (w, u0, qg, p, kd, jnp.exp(last)[..., None]))

    zero = jnp.zeros((b, h_v, k.shape[-1], v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(block_step, zero, (
        blocks(q), blocks(k), blocks(v), blocks(g[..., None]),
        blocks(beta[..., None])))
    # [NB, span, B, H, C, d_v] -> [B, NB, span, C, H, d_v]
    o = jnp.moveaxis(jnp.moveaxis(o, 3, 4), 2, 0)
    return o.reshape((b, nb * span * c) + o.shape[4:])[:, :s]
