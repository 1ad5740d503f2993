"""ResNet family (v1.5), TPU-native flax implementation.

Parity target: the reference benchmark harness trains ResNet-101 from
TF-official models (``/root/reference/examples/benchmark/imagenet.py``);
ResNet-50 is the north-star bench config (BASELINE.json).  Design notes for
TPU: NHWC layout (XLA's native conv layout on TPU), bfloat16 compute with
f32 params/batch-stats, no data-dependent control flow.
"""
from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class ResNetBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1), self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


class BottleneckResNetBlock(nn.Module):
    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1), self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


def space_to_depth(x, block=2):
    """(B, H, W, C) -> (B, H/b, W/b, b*b*C), channel order (dr, dc, c)."""
    B, H, W, C = x.shape
    x = x.reshape(B, H // block, block, W // block, block, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        B, H // block, W // block, block * block * C)


def conv7_to_s2d_kernel(k7):
    """Reparametrize a (7,7,C,F) stride-2 stem kernel into the equivalent
    (4,4,4C,F) kernel for the space-to-depth stem: zero-pad to 8x8 at the
    top-left, then fold each 2x2 tap block into the channel dim.  The two
    stems compute the SAME function (asserted in tests/test_models.py), so
    "space_to_depth" is a layout change, not an architecture change."""
    k8 = jnp.pad(k7, [(1, 0), (1, 0), (0, 0), (0, 0)])
    _, _, C, F = k8.shape
    return k8.reshape(4, 2, 4, 2, C, F).transpose(0, 2, 1, 3, 4, 5).reshape(
        4, 4, 4 * C, F)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    # "conv": the paper's 7x7/s2 stem.  "space_to_depth": the equivalent
    # MXU-friendly form (MLPerf-style): 2x2 space-to-depth packs the
    # 3-channel input into 12 channels, and a 4x4/s1 conv — whose kernel
    # is a pure reindexing of the zero-padded 8x8 stem kernel — computes
    # the identical function with far better MXU lane utilization (3
    # input channels waste 125/128 lanes).
    stem: str = "conv"
    # True: batch-norm reduces mean/var in float32 (flax default; exact).
    # False: stats reduce in the compute dtype (bf16 here) — halves the
    # BN-stat HBM traffic (the norm passes are the largest item of the
    # ResNet-50 step, PERF.md section 5), at a small stats-precision
    # cost.  Never measured on a chip (ROADMAP D11); not the default.
    bn_f32_stats: bool = True
    # "bn": flax nn.BatchNorm (XLA's multi-pass lowering; exact default).
    # "bn_fused": the single-VMEM-pass Pallas batch norm
    #   (ops/pallas/fused_norm.py) — one activation HBM read instead of
    #   three, the F008 memory-bound remediation knob.
    # "gn": fused GroupNorm — per-sample stats, no batch-stats traffic
    #   or running-average state at all (the ":fused_norm"/":gn"
    #   strategy variants of examples/benchmark.py set this argument).
    norm: str = "bn"

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype)
        if self.norm == "bn":
            norm = partial(nn.BatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype,
                           force_float32_reductions=self.bn_f32_stats)
        elif self.norm == "bn_fused":
            from autodist_tpu.models.norm import FusedBatchNorm

            norm = partial(FusedBatchNorm, use_running_average=not train,
                           momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        elif self.norm == "gn":
            from autodist_tpu.models.norm import FusedGroupNorm

            norm = partial(FusedGroupNorm, num_groups=32, epsilon=1e-5,
                           dtype=self.dtype)
        else:
            raise ValueError(f"unknown norm {self.norm!r}")
        x = x.astype(self.dtype)
        if self.stem == "space_to_depth":
            x = space_to_depth(x, 2)
            x = conv(self.num_filters, (4, 4), (1, 1),
                     padding=[(2, 1), (2, 1)], name="conv_init")(x)
        elif self.stem == "conv":
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        else:
            raise ValueError(f"unknown stem {self.stem!r}")
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_size in enumerate(self.stage_sizes):
            for j in range(block_size):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block_cls(self.num_filters * 2 ** i,
                                   conv=conv, norm=norm, act=nn.relu,
                                   strides=strides)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block_cls=ResNetBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=ResNetBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block_cls=BottleneckResNetBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block_cls=BottleneckResNetBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block_cls=BottleneckResNetBlock)
