"""Fused normalization Pallas kernels for the memory-bound ResNet step.

XLA lowers ``nn.BatchNorm`` as separate mean / variance / normalize
passes — three HBM round-trips of the activation.  These kernels fuse
the whole normalization into ONE VMEM pass per channel slab: single-read sum + sum-of-squares moments, rsqrt normalize,
scale-bias, optional activation and optional residual add, so HBM sees
one activation read and one result write.  The F008 (memory-bound)
audit finding names this knob as its remediation.

Batch norm reduces over all rows (batch x spatial) per channel block;
group norm reduces per sample per channel group, with the group
coupling expressed as a small in-VMEM indicator matmul (no lane-dim
reshape, so the kernel stays Mosaic-tileable for ragged group widths).

Both kernels carry a ``jax.custom_vjp``: the backward pass uses the
standard closed-form normalization gradients (plain jnp, f32), so
``jax.grad`` through the fused path matches the unfused reference
(pinned in tests/test_fused_norm.py).

Per the AD10/equarx convention the kernels run in interpreter mode off
TPU (tests, CPU meshes); ``tools/aot_fused_norm.py`` Mosaic-compiles
them for v5e and records the eliminated norm-site HBM bytes.

Kernel playbook: /opt/skills/guides/pallas_guide.md (tiling: f32
(8,128) / bf16 (16,128); whole-slab stats in VMEM; grid over channel
blocks).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128        # channel-block width (TPU lane count)
SUB = 16          # row-padding multiple (bf16 tile sublane)
# The kernels hold one whole slab per grid step — (rows, LANE) for batch
# norm, one sample's (rows, C) for group norm — and Mosaic's scoped VMEM on
# v5e is 16 MiB.  What a slab element costs there: the input and output
# windows, double-buffered, plus an f32 working copy where the input is
# narrower, and the same again for a residual.  Deviceless v5e compiles
# (tests/test_tpu_compile.py) accept bf16 slabs up to 11,024 x 128 and f32
# up to 8,192 x 128, which this model keeps under with 2 MiB to spare for
# the scale/bias/moment blocks and group norm's indicator.  Above the
# budget the module wrappers (models/norm.py) run the reference and say so.
VMEM_BUDGET_BYTES = 14 * 2 ** 20


def _on_tpu():
    return jax.default_backend() == "tpu"


def _pad_to(n, mult):
    return -(-n // mult) * mult


def _slab_fits_vmem(elems, dtype, residual):
    item = jnp.dtype(dtype).itemsize
    per_elem = 4 * item + (4 if item < 4 else 0)
    if residual:
        per_elem += 2 * item + 4
    return elems * per_elem <= VMEM_BUDGET_BYTES


def bn_fits_vmem(x, residual=False):
    """Whether :func:`fused_batch_norm` on ``x`` compiles within VMEM."""
    rows = x.size // x.shape[-1]
    return _slab_fits_vmem(_pad_to(rows, SUB) * LANE, x.dtype, residual)


def gn_fits_vmem(x, residual=False):
    """Whether :func:`fused_group_norm` on ``x`` compiles within VMEM."""
    b, ch = x.shape[0], x.shape[-1]
    rows = x.size // (b * ch)
    return _slab_fits_vmem(_pad_to(rows, SUB) * _pad_to(ch, LANE), x.dtype,
                           residual)


def _apply_act(y, act):
    if act is None:
        return y
    if act == "relu":
        return jnp.maximum(y, 0.0)
    raise ValueError(f"unsupported fused activation {act!r}")


# ---------------------------------------------------------------------------
# fused batch norm
# ---------------------------------------------------------------------------


def _bn_fwd_kernel(n_rows, eps, act, has_residual, *refs):
    if has_residual:
        x_ref, scale_ref, bias_ref, res_ref, y_ref, mean_ref, var_ref = refs
    else:
        x_ref, scale_ref, bias_ref, y_ref, mean_ref, var_ref = refs
        res_ref = None
    # ONE read of the activation slab; moments, normalize, scale-bias,
    # residual and activation all before the single result write.  Rows
    # are zero-padded: they add 0 to both sums, and n_rows is the STATIC
    # true row count.
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.sum(x, axis=0, keepdims=True) / n_rows
    var = jnp.maximum(
        jnp.sum(x * x, axis=0, keepdims=True) / n_rows - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    y = (x - mean) * (inv * scale_ref[0:1, :]) + bias_ref[0:1, :]
    if has_residual:
        y = y + res_ref[:].astype(jnp.float32)
    y = _apply_act(y, act)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = jnp.broadcast_to(mean, mean_ref.shape)
    var_ref[:] = jnp.broadcast_to(var, var_ref.shape)


def _bn_forward(eps, act, interpret, x, scale, bias, residual):
    ch = x.shape[-1]
    rows = x.size // ch
    rp, cp = _pad_to(rows, SUB), _pad_to(ch, LANE)
    x2 = x.reshape(rows, ch)
    if (rp, cp) != (rows, ch):
        x2 = jnp.pad(x2, ((0, rp - rows), (0, cp - ch)))
    # padded channels get zero scale/bias: their (junk-stats) outputs are
    # exactly zero and sliced away below
    sb = [jnp.broadcast_to(
        jnp.pad(v.astype(jnp.float32), (0, cp - ch)), (8, cp))
        for v in (scale, bias)]
    args = [x2] + sb
    row_spec = pl.BlockSpec((rp, LANE), lambda j: (0, j))
    vec_spec = pl.BlockSpec((8, LANE), lambda j: (0, j))
    in_specs = [row_spec, vec_spec, vec_spec]
    if residual is not None:
        r2 = residual.reshape(rows, ch)
        if (rp, cp) != (rows, ch):
            r2 = jnp.pad(r2, ((0, rp - rows), (0, cp - ch)))
        args.append(r2)
        in_specs.append(row_spec)
    y2, mean2, var2 = pl.pallas_call(
        functools.partial(_bn_fwd_kernel, float(rows), eps, act,
                          residual is not None),
        grid=(cp // LANE,),
        in_specs=in_specs,
        out_specs=[row_spec, vec_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct((rp, cp), x.dtype),
                   jax.ShapeDtypeStruct((8, cp), jnp.float32),
                   jax.ShapeDtypeStruct((8, cp), jnp.float32)],
        interpret=interpret,
    )(*args)
    return (y2[:rows, :ch].reshape(x.shape), mean2[0, :ch], var2[0, :ch])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_bn(eps, act, interpret, x, scale, bias, residual):
    return _bn_forward(eps, act, interpret, x, scale, bias, residual)


def _fused_bn_fwd(eps, act, interpret, x, scale, bias, residual):
    y, mean, var = _bn_forward(eps, act, interpret, x, scale, bias, residual)
    return (y, mean, var), (x, scale, mean, var, y, residual)


def _fused_bn_bwd(eps, act, interpret, saved, cts):
    # closed-form BN gradients (f32): dx = inv/N * (N*dxhat - sum(dxhat)
    # - xhat * sum(dxhat * xhat)), with the relu mask taken from the
    # saved POST-activation output and the returned-stats cotangents
    # (gmean/gvar) folded in as their direct d(stat)/dx terms.
    x, scale, mean, var, y, residual = saved
    gy, gmean, gvar = cts
    axes = tuple(range(x.ndim - 1))
    n = float(x.size // x.shape[-1])
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (xf - mean) * inv
    g = gy.astype(jnp.float32)
    if act == "relu":
        g = g * (y > 0).astype(jnp.float32)
    dres = g.astype(residual.dtype) if residual is not None else None
    dbias = jnp.sum(g, axis=axes)
    dscale = jnp.sum(g * xhat, axis=axes)
    dxhat = g * scale.astype(jnp.float32)
    dx = (inv / n) * (n * dxhat - jnp.sum(dxhat, axis=axes, keepdims=True)
                      - xhat * jnp.sum(dxhat * xhat, axis=axes,
                                       keepdims=True))
    if gmean is not None:
        dx = dx + gmean.astype(jnp.float32) / n
    if gvar is not None:
        dx = dx + gvar.astype(jnp.float32) * 2.0 * (xf - mean) / n
    return dx.astype(x.dtype), dscale.astype(scale.dtype), \
        dbias.astype(scale.dtype), dres


_fused_bn.defvjp(_fused_bn_fwd, _fused_bn_bwd)


def fused_batch_norm(x, scale, bias, *, eps=1e-5, act=None, residual=None,
                     interpret=None):
    """Fused training-mode batch norm: ``(y, mean, var)`` with batch
    statistics over all leading dims of ``x``'s ``(..., C)`` layout,
    normalize + scale-bias + optional ``act`` ("relu") + optional
    ``residual`` add in one VMEM pass.  ``interpret=None`` resolves to
    interpreter mode off TPU (the AD10 convention); differentiable via
    the closed-form custom VJP."""
    if interpret is None:
        interpret = not _on_tpu()
    return _fused_bn(float(eps), act, bool(interpret), x, scale, bias,
                     residual)


def batch_norm_reference(x, scale, bias, *, eps=1e-5, act=None,
                         residual=None):
    """The unfused plain-jnp path the kernel must match: separate
    mean / variance / normalize stages, each an HBM round-trip of the
    activation when XLA materializes them."""
    axes = tuple(range(x.ndim - 1))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.maximum(jnp.mean(xf * xf, axes) - mean * mean, 0.0)
    y = (xf - mean) * (jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)) \
        + bias.astype(jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    y = _apply_act(y, act)
    return y.astype(x.dtype), mean, var


# ---------------------------------------------------------------------------
# fused group norm
# ---------------------------------------------------------------------------


def _gn_fwd_kernel(n_per_group, eps, act, has_residual, *refs):
    if has_residual:
        x_ref, p_ref, scale_ref, bias_ref, res_ref, y_ref = refs
    else:
        x_ref, p_ref, scale_ref, bias_ref, y_ref = refs
        res_ref = None
    # one sample per grid step.  Group coupling runs as a tiny indicator
    # matmul on the (1, C) moment vectors: gm = s @ P / n, where
    # P[i, j] = 1 iff channels i, j share a group — no lane-dimension
    # reshape, so any group width compiles.
    x = x_ref[0].astype(jnp.float32)
    s = jnp.sum(x, axis=0, keepdims=True)
    sq = jnp.sum(x * x, axis=0, keepdims=True)
    p = p_ref[:]
    gm = jnp.dot(s, p, preferred_element_type=jnp.float32) / n_per_group
    gsq = jnp.dot(sq, p, preferred_element_type=jnp.float32) / n_per_group
    var = jnp.maximum(gsq - gm * gm, 0.0)
    y = (x - gm) * (jax.lax.rsqrt(var + eps) * scale_ref[0:1, :]) \
        + bias_ref[0:1, :]
    if has_residual:
        y = y + res_ref[0].astype(jnp.float32)
    y = _apply_act(y, act)
    y_ref[0] = y.astype(y_ref.dtype)


def _group_indicator(ch, cp, num_groups):
    """(cp, cp) f32 indicator: 1 where two channels share a group.
    Padded channels each get a unique negative group id, so they couple
    with nothing and their junk stats stay confined."""
    ids = jnp.arange(cp)
    gid = jnp.where(ids < ch, ids // (ch // num_groups), -1 - ids)
    return (gid[:, None] == gid[None, :]).astype(jnp.float32)


def _gn_forward(num_groups, eps, act, interpret, x, scale, bias, residual):
    b, ch = x.shape[0], x.shape[-1]
    rows = x.size // (b * ch)
    rp, cp = _pad_to(rows, SUB), _pad_to(ch, LANE)
    x3 = x.reshape(b, rows, ch)
    if (rp, cp) != (rows, ch):
        x3 = jnp.pad(x3, ((0, 0), (0, rp - rows), (0, cp - ch)))
    p = _group_indicator(ch, cp, num_groups)
    sb = [jnp.broadcast_to(
        jnp.pad(v.astype(jnp.float32), (0, cp - ch)), (8, cp))
        for v in (scale, bias)]
    args = [x3, p] + sb
    slab_spec = pl.BlockSpec((1, rp, cp), lambda b_: (b_, 0, 0))
    vec_spec = pl.BlockSpec((8, cp), lambda b_: (0, 0))
    in_specs = [slab_spec, pl.BlockSpec((cp, cp), lambda b_: (0, 0)),
                vec_spec, vec_spec]
    if residual is not None:
        r3 = residual.reshape(b, rows, ch)
        if (rp, cp) != (rows, ch):
            r3 = jnp.pad(r3, ((0, 0), (0, rp - rows), (0, cp - ch)))
        args.append(r3)
        in_specs.append(slab_spec)
    n_per_group = float(rows * (ch // num_groups))
    y3 = pl.pallas_call(
        functools.partial(_gn_fwd_kernel, n_per_group, eps, act,
                          residual is not None),
        grid=(b,),
        in_specs=in_specs,
        out_specs=slab_spec,
        out_shape=jax.ShapeDtypeStruct((b, rp, cp), x.dtype),
        interpret=interpret,
    )(*args)
    return y3[:, :rows, :ch].reshape(x.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _fused_gn(num_groups, eps, act, interpret, x, scale, bias, residual):
    return _gn_forward(num_groups, eps, act, interpret, x, scale, bias,
                       residual)


def _fused_gn_fwd(num_groups, eps, act, interpret, x, scale, bias, residual):
    y = _gn_forward(num_groups, eps, act, interpret, x, scale, bias, residual)
    return y, (x, scale, y, residual)


def _fused_gn_bwd(num_groups, eps, act, interpret, saved, gy):
    x, scale, y, residual = saved
    b, ch = x.shape[0], x.shape[-1]
    rows = x.size // (b * ch)
    cg = ch // num_groups
    xg = x.reshape(b, rows, num_groups, cg).astype(jnp.float32)
    n = float(rows * cg)
    mean = jnp.mean(xg, axis=(1, 3), keepdims=True)
    var = jnp.maximum(
        jnp.mean(xg * xg, axis=(1, 3), keepdims=True) - mean * mean, 0.0)
    inv = jax.lax.rsqrt(var + eps)
    xhat = (xg - mean) * inv
    g = gy.reshape(b, rows, num_groups, cg).astype(jnp.float32)
    if act == "relu":
        g = g * (y.reshape(b, rows, num_groups, cg) > 0).astype(jnp.float32)
    dres = g.reshape(x.shape).astype(residual.dtype) \
        if residual is not None else None
    dbias = jnp.sum(g, axis=(0, 1)).reshape(ch)
    dscale = jnp.sum(g * xhat, axis=(0, 1)).reshape(ch)
    dxhat = g * scale.astype(jnp.float32).reshape(1, 1, num_groups, cg)
    dx = (inv / n) * (
        n * dxhat - jnp.sum(dxhat, axis=(1, 3), keepdims=True)
        - xhat * jnp.sum(dxhat * xhat, axis=(1, 3), keepdims=True))
    return dx.reshape(x.shape).astype(x.dtype), dscale.astype(scale.dtype), \
        dbias.astype(scale.dtype), dres


_fused_gn.defvjp(_fused_gn_fwd, _fused_gn_bwd)


def fused_group_norm(x, scale, bias, num_groups, *, eps=1e-5, act=None,
                     residual=None, interpret=None):
    """Fused group norm over ``x``'s ``(B, ..., C)`` layout: per-sample
    per-group statistics, normalize + scale-bias + optional activation/
    residual in one VMEM pass per sample.  ``C`` must divide evenly into
    ``num_groups``.  Batch-size independent (no running stats), so the
    same op serves train and eval."""
    ch = x.shape[-1]
    if ch % num_groups:
        raise ValueError(
            f"channels {ch} not divisible into {num_groups} groups")
    if interpret is None:
        interpret = not _on_tpu()
    return _fused_gn(int(num_groups), float(eps), act, bool(interpret),
                     x, scale, bias, residual)


def group_norm_reference(x, scale, bias, num_groups, *, eps=1e-5, act=None,
                         residual=None):
    """Unfused plain-jnp group norm the kernel must match."""
    b, ch = x.shape[0], x.shape[-1]
    rows = x.size // (b * ch)
    cg = ch // num_groups
    xg = x.reshape(b, rows, num_groups, cg).astype(jnp.float32)
    mean = jnp.mean(xg, axis=(1, 3), keepdims=True)
    var = jnp.maximum(
        jnp.mean(xg * xg, axis=(1, 3), keepdims=True) - mean * mean, 0.0)
    y = (xg - mean) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32).reshape(1, 1, num_groups, cg) \
        + bias.astype(jnp.float32).reshape(1, 1, num_groups, cg)
    y = y.reshape(x.shape)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    y = _apply_act(y, act)
    return y.astype(x.dtype)
