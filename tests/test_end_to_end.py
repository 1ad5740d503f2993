"""End-to-end value-exact synchronization tests.

TPU translation of the reference's integration case c0
(``tests/integration/cases/c0.py:88-121``): after a step, the variable must
equal exactly what single-device training on the *global* batch would give —
pinning the semantics of every synchronizer, not just "loss goes down".
Runs on the 8-virtual-device CPU mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from autodist_tpu.autodist import AutoDist
from autodist_tpu.ops.sparse import embedding_lookup
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import (
    PS, AllReduce, Parallax, PartitionedAR, PartitionedPS, PSLoadBalancing,
    RandomAxisPartitionAR, UnevenPartitionedPS,
)

SPEC = ResourceSpec.from_num_chips(8)
RS = np.random.RandomState(0)
BATCH = RS.randn(16, 12).astype(np.float32)


def _loss(p, batch):
    return jnp.mean((batch @ p["w"] + p["b"]) ** 2)


def _params():
    r = np.random.RandomState(7)
    return {"w": jnp.asarray(r.randn(12, 3), jnp.float32),
            "b": jnp.zeros((3,), jnp.float32)}


def _oracle(opt, steps):
    p = _params()
    st = opt.init(p)
    for _ in range(steps):
        g = jax.grad(_loss)(p, jnp.asarray(BATCH))
        u, st = opt.update(g, st, p)
        p = optax.apply_updates(p, u)
    return p


ALL_BUILDERS = [
    AllReduce(chunk_size=1),
    AllReduce(chunk_size=128),
    PS(),
    PS(local_proxy_variable=True),
    PSLoadBalancing(),
    PartitionedPS(max_shards=8),
    UnevenPartitionedPS(max_shards=8),
    PartitionedAR(max_shards=8),
    RandomAxisPartitionAR(max_shards=8, seed=3),
]


@pytest.mark.parametrize("builder", ALL_BUILDERS, ids=lambda b: type(b).__name__ + str(id(b) % 97))
@pytest.mark.parametrize("opt_name", ["sgd", "adam"])
def test_value_exact_sync(builder, opt_name):
    opt = optax.sgd(0.1) if opt_name == "sgd" else optax.adam(0.05)
    ad = AutoDist(resource_spec=SPEC, strategy_builder=builder)
    sess = ad.distribute(_loss, _params(), opt)
    for _ in range(3):
        metrics = sess.run(BATCH)
    exp = _oracle(opt, 3)
    got = sess.params()
    np.testing.assert_allclose(got["w"], exp["w"], atol=2e-5)
    np.testing.assert_allclose(got["b"], exp["b"], atol=2e-5)
    assert sess.step == 3
    assert np.isfinite(float(metrics["loss"]))


def test_sparse_embedding_all_strategies():
    V, D = 50, 4
    r = np.random.RandomState(1)
    table0 = r.randn(V, D).astype(np.float32)
    dense0 = r.randn(D, 2).astype(np.float32)
    ids = r.randint(0, V, size=(16,)).astype(np.int32)

    def loss_fn(p, batch):
        e = embedding_lookup(p["emb"], batch["ids"])
        return jnp.mean((e @ p["proj"]) ** 2)

    def init_p():
        return {"emb": jnp.asarray(table0), "proj": jnp.asarray(dense0)}

    opt = optax.sgd(0.1)
    p = init_p()
    st = opt.init(p)
    for _ in range(2):
        g = jax.grad(loss_fn)(p, {"ids": jnp.asarray(ids)})
        u, st = opt.update(g, st, p)
        p = optax.apply_updates(p, u)

    for builder in [Parallax(), AllReduce(), PS(), PartitionedPS(max_shards=8)]:
        ad = AutoDist(resource_spec=SPEC, strategy_builder=builder)
        sess = ad.distribute(loss_fn, init_p(), opt, sparse_vars=["emb"])
        for _ in range(2):
            sess.run({"ids": ids})
        got = sess.params()
        np.testing.assert_allclose(got["emb"], p["emb"], atol=1e-5,
                                   err_msg=type(builder).__name__)
        np.testing.assert_allclose(got["proj"], p["proj"], atol=1e-5,
                                   err_msg=type(builder).__name__)


@pytest.mark.parametrize("comp,tol", [
    ("NoneCompressor", 1e-6),
    ("HorovodCompressor", 5e-3),
    ("HorovodCompressorEF", 5e-3),
    ("Int8Compressor", 5e-2),
    ("Int8CompressorEF", 5e-2),
])
def test_compressors(comp, tol):
    ad = AutoDist(resource_spec=SPEC, strategy_builder=AllReduce(compressor=comp))
    p = {"w": jnp.ones((64,))}
    sess = ad.distribute(lambda p_, b: jnp.mean(b @ p_["w"]), p, optax.sgd(0.1))
    b = np.random.RandomState(0).randn(16, 64).astype(np.float32)
    sess.run(b)
    got = sess.params()["w"]
    exp = np.ones(64) - 0.1 * b.mean(0)
    assert np.abs(got - exp).max() < tol


def test_error_feedback_residual_carries():
    """EF must track and reinject quantization error over steps."""
    ad = AutoDist(resource_spec=SPEC,
                  strategy_builder=AllReduce(compressor="HorovodCompressorEF"))
    p = {"w": jnp.zeros((32,))}
    sess = ad.distribute(lambda p_, b: jnp.mean(b @ p_["w"]), p, optax.sgd(0.01))
    b = np.full((8, 32), 1.0 + 2**-10, np.float32)  # value bf16 cannot represent
    for _ in range(64):
        jax.block_until_ready(sess.run(b))   # see test_powersgd's docstring
    got = sess.params()["w"]
    exp = -0.01 * 64 * b.mean(0)
    # with EF the accumulated error stays bounded; without it, the 2**-10
    # component would be lost every step (rel err ~1e-3 * 64 steps)
    np.testing.assert_allclose(got, exp, rtol=2e-3)


def test_staleness_local_updates_then_average():
    """PS(staleness=s): devices update locally, global average every s+1
    steps — the SPMD realization of bounded-staleness sync (reference c9)."""
    ad = AutoDist(resource_spec=SPEC, strategy_builder=PS(staleness=1))
    p = {"w": jnp.zeros((8,))}
    sess = ad.distribute(lambda p_, b: jnp.mean(b @ p_["w"]), p, optax.sgd(0.1))
    b = np.arange(16 * 8, dtype=np.float32).reshape(16, 8)
    sess.run(b)
    sess.run(b)
    got = sess.params()["w"]
    # each device does 2 local steps with its local mean; averaging then
    # equals 2 steps with the global mean (linear loss)
    np.testing.assert_allclose(got, -0.2 * b.mean(0), atol=1e-4)


def test_divergent_params_mid_window():
    """Between averaging rounds, device copies legitimately diverge; the
    fetch contract returns their mean."""
    ad = AutoDist(resource_spec=SPEC, strategy_builder=PS(staleness=3))
    p = {"w": jnp.zeros((8,))}
    sess = ad.distribute(lambda p_, b: jnp.mean(b @ p_["w"]), p, optax.sgd(0.1))
    b = np.arange(16 * 8, dtype=np.float32).reshape(16, 8)
    sess.run(b)  # step 1 of a 4-step window: no sync yet
    got = sess.params()["w"]
    np.testing.assert_allclose(got, -0.1 * b.mean(0), atol=1e-4)


def test_multi_step_convergence():
    """Linear regression converges under every family (smoke, c1-style)."""
    r = np.random.RandomState(3)
    X = r.randn(64, 5).astype(np.float32)
    true_w = np.array([3., -1., 2., 0.5, -2.], np.float32)
    y = X @ true_w + 0.01 * r.randn(64).astype(np.float32)

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    for builder in [AllReduce(), PSLoadBalancing(), Parallax()]:
        ad = AutoDist(resource_spec=SPEC, strategy_builder=builder)
        sess = ad.distribute(loss_fn, {"w": jnp.zeros(5), "b": jnp.zeros(())},
                             optax.sgd(0.05))
        for _ in range(200):
            m = jax.block_until_ready(sess.run({"x": X, "y": y}))
        assert float(m["loss"]) < 0.01, type(builder).__name__
        np.testing.assert_allclose(sess.params()["w"], true_w, atol=0.1)


def test_rng_and_aux():
    """has_rng threads a per-device key; has_aux metrics are pmean'd."""
    ad = AutoDist(resource_spec=SPEC, strategy_builder=AllReduce())

    def loss_fn(p, batch, rng):
        noise = jax.random.normal(rng, ())
        loss = jnp.mean(batch @ p["w"])
        return loss, {"noise": noise}

    sess = ad.distribute(loss_fn, {"w": jnp.ones((4,))}, optax.sgd(0.1),
                         has_aux=True, has_rng=True, rng=jax.random.PRNGKey(1))
    m1 = sess.run(np.ones((8, 4), np.float32))
    m2 = sess.run(np.ones((8, 4), np.float32))
    assert "noise" in m1
    # per-step rng folding: different steps see different noise
    assert float(m1["noise"]) != float(m2["noise"])
