"""Mamba-2's selective state-space scan in its chunked (state-space-dual)
form.

Per head ``h`` of ``P`` channels, with a state ``S`` of ``[P, N]`` that
starts at zero, position ``t`` reads ``u_t`` (``[P]``), a step ``dt_t > 0``,
and ``B_t, C_t`` (``[N]``) of the head's group (``H / G`` consecutive heads
share a group's ``B`` and ``C``); ``A < 0`` and ``D`` are the head's own::

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * u_t B_t^T
    y_t = S_t C_t + D * u_t

A scan over positions is the definition (the tests' reference); trained at
thousands of positions it is one rank-one update after another.  Here a chunk
of ``Q`` positions is computed as matrix products.  With ``a = dt * A`` and
``c`` its running sum inside the chunk (``c_i <= 0`` and non-increasing) and
``S_0`` the state the chunk starts from::

    L_ij    = exp(c_i - c_j)            for i >= j, else 0
    Y       = (L o (C B^T)) (dt o u)  +  exp(c_i) o (C S_0^T)  +  D o u
    S_Q     = exp(c_Q) * S_0 + ((exp(c_Q - c) o dt o u)^T B)

Every exponent is a difference ``c_i - c_j`` with ``j <= i`` or a ``c_i``
itself, so none is positive and no ``exp`` overflows.  ``C B^T`` is made once
a group and used by all its heads.  Everything that does not need ``S_0`` (and
the chunks' own states) is computed for a block of chunks at once; a
``lax.scan`` over the chunks carries the state in float32 and is elementwise.
At a chunk and a state of 128 every product has a contraction or an output
of 128.  The products take their operands in ``dtype`` (bfloat16 in
training) and accumulate in float32; the decays, their sums and the carried
state are float32 whatever ``dtype`` is.

That scan form is the definition and the CPU's path.  On a TPU, where
``ops/pallas/ssd.py:tiles`` takes the shapes, two Pallas kernels with the
same equations and precision run instead, which keep a chunk's tiles and the
state in VMEM.
"""
import jax
import jax.numpy as jnp

from autodist_tpu.ops.pallas import flash_attention
from autodist_tpu.ops.pallas import ssd as kernels

# Chunks whose state-free parts are computed at once.  A block is a
# ``jax.checkpoint``: the backward pass keeps the block's inputs and the
# state at its start and computes its intermediates again, so what is live
# is one block's ``L`` tiles and not the sequence's (at 16,384 tokens of 64
# heads those are 0.54 GB in float32, PERF.md section 4b).
CHUNKS_PER_BLOCK = 16


def _mm(spec, a, b, dtype):
    """``einsum`` with operands in ``dtype`` and a float32 result."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def ssd_chunked(u, dt, a, b, c, d, chunk=128, dtype=None):
    """The scan over whole sequences, from a zero state.

    Args:
      u: ``[B, S, H, P]``.
      dt: ``[B, S, H]`` steps, ``> 0`` (after the softplus), float32.
      a: ``[H]``, ``< 0``, float32; d: ``[H]`` the skip weights.
      b, c: ``[B, S, G, N]`` with ``H`` a multiple of ``G``; group ``g``
        serves the heads ``g * (H // G) ...``.
      chunk: positions a chunk; the sequence is padded to whole blocks with
        positions of step 0, which neither decay the state nor write to it.
      dtype: the matrix products' operand type; default ``u.dtype``.

    Returns ``y`` of ``[B, S, H, P]`` in ``dtype``.
    """
    dtype = jnp.dtype(dtype or u.dtype)
    bsz, s, h, p = u.shape
    g, n = b.shape[2:]
    rep = h // g
    if rep * g != h:
        raise ValueError(f"{h} heads over {g} groups")
    if _kernels_take(chunk, h, p, g, n, dtype):
        return kernels.ssd_scan(u, dt, a, b, c, d, chunk, dtype)
    q = min(chunk, s)
    span = min(CHUNKS_PER_BLOCK, -(-s // q))
    nb = -(-s // (q * span))

    def blocks(x):
        """``[B, S, ...]`` -> ``[NB, B, span, Q, ...]``, zero-padded."""
        x = jnp.pad(x, [(0, 0), (0, nb * span * q - s)]
                    + [(0, 0)] * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((bsz, nb, span, q) + x.shape[2:]), 1, 0)

    lower = jnp.tril(jnp.ones((q, q), bool))
    a = a.astype(jnp.float32)
    skip = d.astype(jnp.float32).reshape(g, rep, 1)

    def carry(state, x):
        own, decay = x
        return decay * state + own, state

    @jax.checkpoint
    def block_step(state, x):
        u, dt, b, c = x              # [B, span, Q, H | G, ...]
        dt = dt.astype(jnp.float32)
        cum = jnp.cumsum(dt * a, axis=2)                    # [B,K,Q,H]
        last = cum[:, :, -1:]
        cum_t = jnp.moveaxis(cum, 2, 3)                     # [B,K,H,Q]
        tile = jnp.exp(jnp.where(
            lower, cum_t[..., :, None] - cum_t[..., None, :], -jnp.inf))
        cb = _mm("bkign,bkjgn->bkgij", c, b, dtype)         # once a group
        m = (tile.reshape(tile.shape[:2] + (g, rep, q, q))
             * cb[:, :, :, None]).astype(dtype)
        uf = u.astype(jnp.float32).reshape(u.shape[:3] + (g, rep, p))

        def heads(t):                # [B,K,Q,H] -> [B,K,Q,G,rep,1]
            return t.reshape(t.shape[:3] + (g, rep, 1))

        y = _mm("bkgrij,bkjgrp->bkigrp", m, heads(dt) * uf, dtype)
        own = _mm("bkjgrp,bkjgn->bkgrpn",
                  heads(jnp.exp(last - cum) * dt) * uf, b, dtype)
        decay = jnp.exp(last[:, :, 0]).reshape(last.shape[:2] + (g, rep, 1,
                                                                 1))
        state, before = jax.lax.scan(
            carry, state, (jnp.moveaxis(own, 1, 0),
                           jnp.moveaxis(decay, 1, 0)))
        y = y + heads(jnp.exp(cum)) * _mm(
            "bkign,kbgrpn->bkigrp", c, before, dtype)
        return state, (y + skip * uf).astype(dtype)

    zero = jnp.zeros((bsz, g, rep, p, n), jnp.float32)
    _, y = jax.lax.scan(block_step, zero,
                        (blocks(u), blocks(dt), blocks(b), blocks(c)))
    # [NB, B, span, Q, G, rep, P] -> [B, S, H, P]
    return jnp.moveaxis(y, 0, 1).reshape(bsz, nb * span * q, h, p)[:, :s]


def ssd_chunked_packed(xbc, dt, a, d, groups, state, chunk=128, dtype=None):
    """``ssd_chunked`` over ``xbc`` of ``[B, S, H * P + 2 * G * N]``: ``u``,
    ``B`` and ``C`` side by side, as the mixer's convolution leaves them
    (``G = groups``, ``N = state``).  The kernels read the three where they
    lie, so that no slice of ``xbc`` is copied out for them."""
    dtype = jnp.dtype(dtype or xbc.dtype)
    h = dt.shape[2]
    p = (xbc.shape[2] - 2 * groups * state) // h
    if _kernels_take(chunk, h, p, groups, state, dtype):
        return kernels.ssd_scan_packed(xbc, dt, a, d, groups, state, chunk,
                                       dtype)
    bsz, s, _ = xbc.shape
    inner, gn = h * p, groups * state
    return ssd_chunked(xbc[..., :inner].reshape(bsz, s, h, p), dt, a,
                       xbc[..., inner:inner + gn].reshape(bsz, s, groups,
                                                          state),
                       xbc[..., inner + gn:].reshape(bsz, s, groups, state),
                       d, chunk, dtype)


def _kernels_take(chunk, h, p, g, n, dtype):
    """The kernels on a TPU (asked as the flash kernels ask) where they tile
    the chunk, the state and a group's heads inside VMEM; the scan form
    everywhere else, and the definition."""
    return (flash_attention._on_tpu() and (h * p) % n == 0
            and kernels.tiles(chunk, p, h // g, n, dtype.itemsize))

