"""Flax wrappers for the fused Pallas normalization kernels.

``FusedBatchNorm`` is a drop-in for ``nn.BatchNorm`` at ResNet's call
sites (same params/batch_stats collections, momentum EMA, eval path on
running stats) whose TRAINING path computes batch statistics +
normalize + scale-bias in one VMEM pass
(:func:`autodist_tpu.ops.pallas.fused_norm.fused_batch_norm`) instead
of XLA's three-HBM-round-trip lowering — the remediation the F008
(memory-bound) audit finding names.  ``FusedGroupNorm`` removes the
batch-statistics HBM traffic entirely (per-sample groups, no running
stats, train == eval).

Both run the unfused reference path, and log the site once, when a slab
would not fit VMEM (``fused_norm.bn_fits_vmem`` / ``gn_fits_vmem`` — at
ResNet-50 B=256 that is every batch-norm site), or when
``impl="reference"`` forces it for equivalence tests; off TPU the kernels
run in interpreter mode.
"""
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu.ops.pallas.fused_norm import (batch_norm_reference,
                                                bn_fits_vmem,
                                                fused_batch_norm,
                                                fused_group_norm,
                                                gn_fits_vmem,
                                                group_norm_reference)
from autodist_tpu.utils import logging


def _use_kernel(module, x, fits):
    """``impl="kernel"`` and the slab fits VMEM; a site that does not fit
    is logged once, at trace time, and runs the reference."""
    if module.impl != "kernel":
        return False
    if not fits(x):
        logging.warning_once(
            "%s on %s %s: slab exceeds the kernel's VMEM budget; running "
            "the unfused reference at this site", type(module).__name__,
            tuple(x.shape), str(x.dtype))
        return False
    return True


class FusedBatchNorm(nn.Module):
    """``nn.BatchNorm``-compatible module over the fused Pallas kernel."""

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = None
    scale_init: Callable = nn.initializers.ones
    bias_init: Callable = nn.initializers.zeros
    impl: str = "kernel"        # "kernel" | "reference"

    @nn.compact
    def __call__(self, x):
        ch = x.shape[-1]
        scale = self.param("scale", self.scale_init, (ch,), jnp.float32)
        bias = self.param("bias", self.bias_init, (ch,), jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda: jnp.zeros((ch,), jnp.float32))
        ra_var = self.variable("batch_stats", "var",
                               lambda: jnp.ones((ch,), jnp.float32))
        out_dtype = self.dtype or x.dtype
        if self.use_running_average:
            inv = jax.lax.rsqrt(ra_var.value + self.epsilon) * scale
            y = (x.astype(jnp.float32) - ra_mean.value) * inv + bias
            return y.astype(out_dtype)
        if _use_kernel(self, x, bn_fits_vmem):
            y, mean, var = fused_batch_norm(x, scale, bias,
                                            eps=self.epsilon)
        else:
            y, mean, var = batch_norm_reference(x, scale, bias,
                                                eps=self.epsilon)
        if not self.is_initializing():
            m = self.momentum
            ra_mean.value = m * ra_mean.value + (1 - m) * \
                jax.lax.stop_gradient(mean)
            ra_var.value = m * ra_var.value + (1 - m) * \
                jax.lax.stop_gradient(var)
        return y.astype(out_dtype)


class FusedGroupNorm(nn.Module):
    """GroupNorm over the fused kernel: per-sample statistics, so the
    batch-stats HBM traffic (and its cross-replica skew) disappears and
    train == eval — the BN→GN lever of the F008 remediation."""

    num_groups: int = 32
    epsilon: float = 1e-5
    dtype: Any = None
    scale_init: Callable = nn.initializers.ones
    bias_init: Callable = nn.initializers.zeros
    impl: str = "kernel"

    @nn.compact
    def __call__(self, x):
        ch = x.shape[-1]
        groups = self.num_groups if ch % self.num_groups == 0 else \
            (ch if ch < self.num_groups else 1)
        scale = self.param("scale", self.scale_init, (ch,), jnp.float32)
        bias = self.param("bias", self.bias_init, (ch,), jnp.float32)
        if _use_kernel(self, x, gn_fits_vmem):
            y = fused_group_norm(x, scale, bias, groups, eps=self.epsilon)
        else:
            y = group_norm_reference(x, scale, bias, groups,
                                     eps=self.epsilon)
        return y.astype(self.dtype or x.dtype)
