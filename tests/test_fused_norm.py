"""Fused Pallas normalization kernels (autodist_tpu/ops/pallas/fused_norm.py).

Interpret-mode drives on CPU: the fused batch-norm kernel (stats +
normalize + scale-bias + epilogue in one VMEM pass) must be allclose-
equivalent to the unfused reference — forward AND backward, across
dtypes and epilogues — and the GroupNorm variant likewise.  The flax
modules (models/norm.py) must track nn.BatchNorm / stay drop-in under
the ResNet ``norm`` knob, and the committed v5e AOT lever record must
keep its >= 30% byte-removal claim.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops.pallas.fused_norm import (batch_norm_reference,
                                                bn_fits_vmem,
                                                fused_batch_norm,
                                                fused_group_norm,
                                                group_norm_reference)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


def _mk(shape, dtype, seed=0):
    r = np.random.RandomState(seed)
    return jnp.asarray(r.randn(*shape), dtype)


def jit_grad(f, **kw):
    return jax.jit(jax.grad(f, **kw))


# -- fused batch norm: forward equivalence -----------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act,residual", [(None, False), ("relu", False),
                                          ("relu", True)])
def test_fused_bn_forward_matches_reference(dtype, act, residual):
    x = _mk((4, 6, 6, 64), dtype)
    scale = _mk((64,), jnp.float32, 1) * 0.1 + 1.0
    bias = _mk((64,), jnp.float32, 2) * 0.1
    res = _mk(x.shape, dtype, 3) if residual else None
    y, mean, var = fused_batch_norm(x, scale, bias, act=act, residual=res)
    y_ref, mean_ref, var_ref = batch_norm_reference(
        x, scale, bias, act=act, residual=res)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))
    np.testing.assert_allclose(mean, mean_ref, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(var, var_ref, atol=1e-4, rtol=1e-3)


def test_fused_bn_odd_shapes_pad_correctly():
    # rows not a SUB multiple, channels not a LANE multiple: the kernel's
    # zero-padding must not leak into the moments or the outputs
    x = _mk((3, 5, 5, 17), jnp.float32)
    scale = jnp.ones((17,)) * 1.3
    bias = jnp.zeros((17,)) + 0.2
    y, mean, var = fused_batch_norm(x, scale, bias)
    y_ref, mean_ref, var_ref = batch_norm_reference(x, scale, bias)
    np.testing.assert_allclose(y, y_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(mean, mean_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(var, var_ref, atol=1e-5, rtol=1e-5)


# -- fused batch norm: backward (custom_vjp) equivalence ---------------------


@pytest.mark.parametrize("act,residual", [(None, False), ("relu", False),
                                          ("relu", True)])
def test_fused_bn_grad_matches_reference(act, residual):
    x = _mk((2, 4, 4, 32), jnp.float32)
    scale = _mk((32,), jnp.float32, 1) * 0.1 + 1.0
    bias = _mk((32,), jnp.float32, 2) * 0.1
    res = _mk(x.shape, jnp.float32, 3) if residual else None
    w = _mk(x.shape, jnp.float32, 4)  # non-uniform cotangent

    def loss(fn, x, s, b, r):
        y = fn(x, s, b, act=act, residual=r)[0]
        return jnp.sum(y * w)

    g_fused = jit_grad(lambda *a: loss(fused_batch_norm, *a),
                       argnums=(0, 1, 2))(x, scale, bias, res)
    g_ref = jit_grad(lambda *a: loss(batch_norm_reference, *a),
                     argnums=(0, 1, 2))(x, scale, bias, res)
    for gf, gr in zip(g_fused, g_ref):
        np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=5e-4)


def test_fused_bn_grad_bf16_tracks_reference():
    x = _mk((2, 4, 4, 32), jnp.bfloat16)
    scale = jnp.ones((32,))
    bias = jnp.zeros((32,))

    def loss(fn, x):
        return jnp.sum(fn(x, scale, bias, act="relu")[0].astype(jnp.float32))

    gf = jit_grad(lambda x: loss(fused_batch_norm, x))(x)
    gr = jit_grad(lambda x: loss(batch_norm_reference, x))(x)
    np.testing.assert_allclose(np.asarray(gf, np.float32),
                               np.asarray(gr, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_fused_bn_residual_cotangent_flows():
    x = _mk((2, 4, 4, 16), jnp.float32)
    res = _mk(x.shape, jnp.float32, 1)
    scale, bias = jnp.ones((16,)), jnp.zeros((16,))

    def loss(fn, r):
        return jnp.sum(fn(x, scale, bias, act="relu", residual=r)[0])

    gf = jit_grad(lambda r: loss(fused_batch_norm, r))(res)
    gr = jit_grad(lambda r: loss(batch_norm_reference, r))(res)
    np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=5e-4)


# -- fused group norm --------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("groups", [4, 32])
def test_fused_gn_forward_matches_reference(dtype, groups):
    x = _mk((2, 6, 6, 64), dtype)
    scale = _mk((64,), jnp.float32, 1) * 0.1 + 1.0
    bias = _mk((64,), jnp.float32, 2) * 0.1
    y = fused_group_norm(x, scale, bias, groups, act="relu")
    y_ref = group_norm_reference(x, scale, bias, groups, act="relu")
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


def test_fused_gn_grad_matches_reference():
    x = _mk((2, 4, 4, 32), jnp.float32)
    scale = _mk((32,), jnp.float32, 1) * 0.1 + 1.0
    bias = _mk((32,), jnp.float32, 2) * 0.1
    w = _mk(x.shape, jnp.float32, 4)

    def loss(fn, x, s, b):
        return jnp.sum(fn(x, s, b, 8, act="relu") * w)

    g_fused = jit_grad(lambda *a: loss(fused_group_norm, *a),
                       argnums=(0, 1, 2))(x, scale, bias)
    g_ref = jit_grad(lambda *a: loss(group_norm_reference, *a),
                     argnums=(0, 1, 2))(x, scale, bias)
    for gf, gr in zip(g_fused, g_ref):
        np.testing.assert_allclose(gf, gr, atol=5e-4, rtol=5e-4)


def test_fused_gn_rejects_indivisible_groups():
    x = _mk((2, 4, 4, 30), jnp.float32)
    with pytest.raises(ValueError):
        fused_group_norm(x, jnp.ones((30,)), jnp.zeros((30,)), 4)


# -- flax modules (models/norm.py) -------------------------------------------


def test_fused_batch_norm_module_tracks_nn_batchnorm():
    import flax.linen as nn

    from autodist_tpu.models import FusedBatchNorm

    x = _mk((4, 8, 8, 16), jnp.float32)
    fused = FusedBatchNorm(use_running_average=False, momentum=0.9)
    plain = nn.BatchNorm(use_running_average=False, momentum=0.9)
    vf = fused.init(jax.random.PRNGKey(0), x)
    vp = plain.init(jax.random.PRNGKey(0), x)
    yf, mf = fused.apply(vf, x, mutable=["batch_stats"])
    yp, mp = plain.apply(vp, x, mutable=["batch_stats"])
    np.testing.assert_allclose(yf, yp, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(mf["batch_stats"]["mean"],
                               mp["batch_stats"]["mean"],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(mf["batch_stats"]["var"],
                               mp["batch_stats"]["var"],
                               atol=1e-5, rtol=1e-5)
    # eval path: running stats, no mutation
    ye = FusedBatchNorm(use_running_average=True, momentum=0.9).apply(
        {"params": vf["params"], "batch_stats": mf["batch_stats"]}, x)
    pe = nn.BatchNorm(use_running_average=True, momentum=0.9).apply(
        {"params": vp["params"], "batch_stats": mp["batch_stats"]}, x)
    np.testing.assert_allclose(ye, pe, atol=2e-5, rtol=2e-5)


def test_fused_module_falls_back_above_vmem_budget(monkeypatch):
    from autodist_tpu.models import FusedBatchNorm
    from autodist_tpu.utils import logging

    # a row slab over the kernel's VMEM budget: the module must take the
    # reference path, say so once with the site's shape, and still agree
    x = _mk((8192, 1, 1, 8), jnp.float32)
    assert bn_fits_vmem(x[:4096]) and not bn_fits_vmem(x)
    said = []
    monkeypatch.setattr(logging, "warning",
                        lambda msg, *a: said.append(msg % a))
    mod = FusedBatchNorm(use_running_average=False)
    v = mod.init(jax.random.PRNGKey(0), x)
    y, _ = mod.apply(v, x, mutable=["batch_stats"])
    y_ref, _, _ = batch_norm_reference(
        x, v["params"]["scale"], v["params"]["bias"])
    np.testing.assert_allclose(y, y_ref, atol=2e-5, rtol=2e-5)
    assert len(said) == 1 and "(8192, 1, 1, 8)" in said[0] \
        and "reference" in said[0]


def test_resnet_norm_knob_bn_fused_matches_bn():
    from autodist_tpu.models.resnet import ResNet, ResNetBlock

    def tiny(norm):
        return ResNet(stage_sizes=[1], block_cls=ResNetBlock,
                      num_classes=10, num_filters=8, dtype=jnp.float32,
                      norm=norm)

    def rename(tree):
        # same params, different auto-scope names: BatchNorm_k vs
        # FusedBatchNorm_k (explicit names bn_init/norm_proj are shared)
        if isinstance(tree, dict):
            return {k.replace("BatchNorm", "FusedBatchNorm"): rename(v)
                    for k, v in tree.items()}
        return tree

    x = _mk((2, 16, 16, 3), jnp.float32)
    v = tiny("bn").init(jax.random.PRNGKey(0), x, train=False)
    out_bn, _ = tiny("bn").apply(v, x, train=True, mutable=["batch_stats"])
    out_fused, _ = tiny("bn_fused").apply(rename(v), x, train=True,
                                          mutable=["batch_stats"])
    np.testing.assert_allclose(out_bn, out_fused, atol=1e-4, rtol=1e-4)


def test_resnet_norm_knob_gn_runs_and_unknown_raises():
    from autodist_tpu.models.resnet import ResNet, ResNetBlock

    x = _mk((2, 16, 16, 3), jnp.float32)
    gn = ResNet(stage_sizes=[1], block_cls=ResNetBlock, num_classes=10,
                num_filters=8, dtype=jnp.float32, norm="gn")
    v = gn.init(jax.random.PRNGKey(0), x, train=False)
    out = gn.apply(v, x, train=True)
    assert out.shape == (2, 10) and np.isfinite(np.asarray(out)).all()
    bad = ResNet(stage_sizes=[1], block_cls=ResNetBlock, num_classes=10,
                 num_filters=8, dtype=jnp.float32, norm="layernorm")
    with pytest.raises(ValueError):
        bad.init(jax.random.PRNGKey(0), x, train=False)


# -- the committed v5e AOT lever record --------------------------------------


def test_fused_norm_lever_record_holds_the_byte_claim():
    """The committed deviceless-compile record must keep the acceptance
    bar: >= 30% of the norm site's XLA-counted HBM bytes removed, the
    fused side floored honestly at argument+output bytes (the custom
    call is opaque to cost_analysis), roofline no worse."""
    path = os.path.join(REPO, "records", "v5e_aot", "fused_norm_lever.json")
    with open(path) as f:
        rec = json.load(f)
    fused, ref = rec["fused_kernel"], rec["unfused_reference"]
    floor = fused["argument_size_in_bytes"] + fused["output_size_in_bytes"]
    assert fused["hbm_bytes_floor"] == max(fused["xla_bytes_accessed"],
                                           floor)
    removed = ref["xla_bytes_accessed"] - fused["hbm_bytes_floor"]
    assert rec["hbm_bytes_removed"] == round(removed)
    frac = removed / ref["xla_bytes_accessed"]
    assert frac >= 0.30
    assert rec["hbm_bytes_removed_frac"] == pytest.approx(frac, abs=1e-4)
    assert fused["roofline_us"] <= ref["roofline_us"]
    assert rec["group_norm_variant"]["mosaic_compiles"] is True
