"""Model zoo smoke + integration tests (tiny shapes, 8-device CPU mesh).

Mirrors the reference's integration cases: c1/c5 (Keras classifier), c2
(sparse embeddings + Adam), c6 (LSTM), plus the benchmark families."""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.autodist import AutoDist
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce, Parallax, PartitionedPS, PSLoadBalancing
from autodist_tpu.models import (
    BERT_TINY, DenseNet121, InceptionV3, LMConfig, NCFConfig,
    ResNet18, ResNet50, VGG16,
)
from autodist_tpu.models import train_lib

SPEC = ResourceSpec.from_num_chips(8)


def _img_batch(n=8, hw=32, classes=10):
    r = np.random.RandomState(0)
    return {"image": r.randn(n, hw, hw, 3).astype(np.float32),
            "label": r.randint(0, classes, n)}


def test_resnet18_trains_with_batch_stats():
    model = ResNet18(num_classes=10, num_filters=8, dtype=jnp.float32)
    loss_fn, params, state = train_lib.classifier_capture(model, (32, 32, 3))
    assert "batch_stats" in state
    ad = AutoDist(resource_spec=SPEC, strategy_builder=AllReduce())
    sess = ad.distribute(loss_fn, params, optax.sgd(0.1), mutable_state=state)
    losses = [float(sess.run(_img_batch())["loss"]) for _ in range(5)]
    assert losses[-1] < losses[0]
    bn = sess.mutable_state()["batch_stats"]
    assert np.any(bn["bn_init"]["mean"] != 0)  # stats updated + synced


def test_bf16_bn_stats_close_to_f32():
    """``ResNet(bn_f32_stats=False)`` (reduce BN stats in the compute
    dtype) stays numerically close to the exact f32-stats model at init
    and still trains."""
    r = np.random.RandomState(1)
    x = jnp.asarray(r.randn(8, 32, 32, 3), jnp.float32)
    outs = {}
    for f32 in (True, False):
        model = ResNet18(num_classes=10, num_filters=8, dtype=jnp.bfloat16,
                         bn_f32_stats=f32)
        v = model.init(jax.random.PRNGKey(0), x, train=True)
        y, _ = jax.jit(lambda v, x: model.apply(
            v, x, train=True, mutable=["batch_stats"]))(v, x)
        outs[f32] = np.asarray(y, np.float32)
    # same function up to bf16 stats rounding
    np.testing.assert_allclose(outs[True], outs[False], atol=0.15)
    model = ResNet18(num_classes=10, num_filters=8, dtype=jnp.float32,
                     bn_f32_stats=False)
    loss_fn, params, state = train_lib.classifier_capture(model, (32, 32, 3))
    ad = AutoDist(resource_spec=SPEC, strategy_builder=AllReduce())
    sess = ad.distribute(loss_fn, params, optax.sgd(0.1), mutable_state=state)
    losses = [float(sess.run(_img_batch())["loss"]) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


@pytest.mark.parametrize("model_fn,kwargs", [
    (ResNet50, dict(num_classes=10, num_filters=4, dtype=jnp.float32)),
    (DenseNet121, dict(num_classes=10, growth_rate=4, dtype=jnp.float32)),
])
def test_deep_cnn_one_step(model_fn, kwargs):
    model = model_fn(**kwargs)
    loss_fn, params, state = train_lib.classifier_capture(model, (32, 32, 3))
    ad = AutoDist(resource_spec=SPEC, strategy_builder=PSLoadBalancing())
    sess = ad.distribute(loss_fn, params, optax.sgd(0.01), mutable_state=state)
    m = sess.run(_img_batch())
    assert np.isfinite(float(m["loss"]))


def test_vgg16_partitioned_fc():
    """VGG's giant fc layers under PartitionedPS (the reference's stress case)."""
    model = VGG16(num_classes=10, dtype=jnp.float32)
    loss_fn, params, state = train_lib.classifier_capture(model, (32, 32, 3))
    assert state == {} or state is None  # VGG has no batch stats
    ad = AutoDist(resource_spec=SPEC, strategy_builder=PartitionedPS(max_shards=8))
    sess = ad.distribute(loss_fn, params, optax.sgd(0.01))
    m = sess.run(_img_batch())
    assert np.isfinite(float(m["loss"]))


@pytest.mark.integration
def test_inception_v3_one_step():
    model = InceptionV3(num_classes=10, dtype=jnp.float32)
    loss_fn, params, state = train_lib.classifier_capture(model, (96, 96, 3))
    ad = AutoDist(resource_spec=SPEC, strategy_builder=AllReduce())
    sess = ad.distribute(loss_fn, params, optax.sgd(0.01), mutable_state=state)
    r = np.random.RandomState(0)
    m = sess.run({"image": r.randn(8, 96, 96, 3).astype(np.float32),
                  "label": r.randint(0, 10, 8)})
    assert np.isfinite(float(m["loss"]))


def test_bert_tiny_pretraining():
    loss_fn, params, sparse = train_lib.bert_capture(BERT_TINY, seq_len=32)
    ad = AutoDist(resource_spec=SPEC, strategy_builder=Parallax())
    sess = ad.distribute(loss_fn, params, optax.adamw(1e-3),
                         sparse_vars=sparse, has_rng=True)
    r = np.random.RandomState(0)
    b = {"input_ids": r.randint(0, 1024, (16, 32)).astype(np.int32),
         "labels": np.where(r.rand(16, 32) < 0.15,
                            r.randint(0, 1024, (16, 32)), -100).astype(np.int32),
         "next_sentence_label": r.randint(0, 2, (16,)).astype(np.int32)}
    losses = [float(sess.run(b)["loss"]) for _ in range(5)]
    assert losses[-1] < losses[0]


def test_lstm_lm_partitioned_embedding():
    cfg = LMConfig(vocab_size=200, embed_dim=16, hidden_dim=32, num_layers=1)
    loss_fn, params, sparse = train_lib.lm_capture(cfg, seq_len=16)
    ad = AutoDist(resource_spec=SPEC, strategy_builder=PartitionedPS(max_shards=8))
    sess = ad.distribute(loss_fn, params, optax.adam(1e-2), sparse_vars=sparse)
    r = np.random.RandomState(0)
    b = {"tokens": r.randint(0, 200, (16, 16)).astype(np.int32),
         "targets": r.randint(0, 200, (16, 16)).astype(np.int32)}
    losses = [float(sess.run(b)["loss"]) for _ in range(5)]
    assert losses[-1] < losses[0]


def test_ncf():
    cfg = NCFConfig(num_users=100, num_items=50, mf_dim=8, mlp_dims=(16, 8))
    loss_fn, params, sparse = train_lib.ncf_capture(cfg)
    ad = AutoDist(resource_spec=SPEC, strategy_builder=Parallax())
    sess = ad.distribute(loss_fn, params, optax.adam(1e-2), sparse_vars=sparse)
    r = np.random.RandomState(0)
    b = {"user": r.randint(0, 100, (32,)).astype(np.int32),
         "item": r.randint(0, 50, (32,)).astype(np.int32),
         "label": (r.rand(32) < 0.5).astype(np.float32)}
    losses = [float(sess.run(b)["loss"]) for _ in range(8)]
    assert losses[-1] < losses[0]


def test_space_to_depth_stem_is_exact_reparametrization():
    """The s2d stem computes the IDENTICAL function to the 7x7/s2 stem
    under the kernel reindexing — a layout change, not an architecture
    change (the MXU-friendly MLPerf-style stem)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from autodist_tpu.models.resnet import (ResNet50, conv7_to_s2d_kernel,
                                            space_to_depth)

    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(2, 64, 64, 3), jnp.float32)

    m_conv = ResNet50(num_classes=10, dtype=jnp.float32)
    m_s2d = ResNet50(num_classes=10, dtype=jnp.float32,
                     stem="space_to_depth")
    v = m_conv.init(jax.random.PRNGKey(0), x, train=False)
    v2 = jax.eval_shape(lambda: m_s2d.init(jax.random.PRNGKey(0), x,
                                           train=False))
    # copy every param; replace the stem kernel with its reindexing
    p2 = jax.tree.map(lambda a: a, v["params"])
    assert v2["params"]["conv_init"]["kernel"].shape == (4, 4, 12, 64)
    p2["conv_init"] = {"kernel": conv7_to_s2d_kernel(
        v["params"]["conv_init"]["kernel"])}
    stats = {k: w for k, w in v.items() if k != "params"}
    y1 = jax.jit(lambda p: m_conv.apply({"params": p, **stats}, x,
                                        train=False))(v["params"])
    y2 = jax.jit(lambda p: m_s2d.apply({"params": p, **stats}, x,
                                       train=False))(p2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-4, rtol=2e-4)

    # and the primitive round-trips shapes as documented
    s = space_to_depth(x, 2)
    assert s.shape == (2, 32, 32, 12)


def test_remat_is_value_exact():
    """config.remat wraps each transformer block in nn.remat: identical
    loss (bitwise — the forward really is the same program) and gradients
    equal to float32 round-off, only peak activation memory changes.

    Gradients are NOT bitwise-reproducible under remat: the backward pass
    interleaves recomputed-forward ops with gradient ops, so XLA fuses and
    reassociates the float32 reductions differently than in the plain
    backward (measured deviation ~4e-8 on ~1e-3 gradients — pure
    round-off; an exact-equality assert here was a wrong expectation, not
    a regression)."""
    from autodist_tpu.models import bert, gpt

    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 32)))
    cfg0 = gpt.GPT_TINY
    cfg1 = gpt.GPTConfig(**{**cfg0.__dict__, "remat": True})
    params = gpt.GPT(cfg0).init(jax.random.PRNGKey(0), tokens)["params"]

    def loss(cfg, p):
        return gpt.gpt_loss(gpt.GPT(cfg).apply({"params": p}, tokens), tokens)

    l0, g0 = jax.jit(jax.value_and_grad(lambda p: loss(cfg0, p)))(params)
    l1, g1 = jax.jit(jax.value_and_grad(lambda p: loss(cfg1, p)))(params)
    assert float(jnp.abs(l0 - l1)) == 0.0
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5,
                                                         rtol=1e-4),
                 g0, g1)

    bcfg0 = bert.BertConfig(**{**bert.BERT_TINY.__dict__,
                               "dtype": jnp.float32})
    bcfg1 = bert.BertConfig(**{**bcfg0.__dict__, "remat": True})
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 1024, (2, 32)))
    m0, m1 = bert.Bert(bcfg0), bert.Bert(bcfg1)
    p = m0.init(jax.random.PRNGKey(0), ids)["params"]
    def f0(p_):
        return jnp.sum(jnp.sin(m0.apply({"params": p_}, ids)[0]))

    def f1(p_):
        return jnp.sum(jnp.sin(m1.apply({"params": p_}, ids)[0]))
    v0, gg0 = jax.jit(jax.value_and_grad(f0))(p)
    v1, gg1 = jax.jit(jax.value_and_grad(f1))(p)
    assert float(jnp.abs(v0 - v1)) == 0.0
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5,
                                                         rtol=1e-4),
                 gg0, gg1)
