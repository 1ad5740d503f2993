"""GPT causal decoder: causality, training, and sequence-parallel (causal
ring attention) trajectory parity with data parallelism."""
import jax
import jax.numpy as jnp
import numpy as np
import optax

from autodist_tpu.autodist import AutoDist
from autodist_tpu.models import GPT, GPTConfig
from autodist_tpu.models import train_lib
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce

CFG = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                intermediate_size=64, max_position=64, dropout_rate=0.0,
                dtype=jnp.float32)
SEQ, B = 16, 8


def _batch(seed=0):
    r = np.random.RandomState(seed)
    toks = r.randint(0, CFG.vocab_size, (B, SEQ + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def test_causality():
    """Changing a future token must not change logits at earlier positions."""
    model = GPT(CFG)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, SEQ), jnp.int32))["params"]
    toks = _batch()["tokens"][:1]
    logits = model.apply({"params": params}, jnp.asarray(toks))
    toks2 = np.array(toks)
    toks2[0, -1] = (toks2[0, -1] + 1) % CFG.vocab_size
    logits2 = model.apply({"params": params}, jnp.asarray(toks2))
    np.testing.assert_allclose(logits[:, :-1], logits2[:, :-1], atol=1e-6)
    assert np.abs(np.asarray(logits[:, -1]) - np.asarray(logits2[:, -1])).max() > 1e-4


def test_gpt_trains_dp():
    loss_fn, params, sparse = train_lib.gpt_capture(CFG, SEQ)
    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(8),
                  strategy_builder=AllReduce())
    sess = ad.distribute(loss_fn, params, optax.adam(1e-2),
                         sparse_vars=sparse, has_rng=True)
    losses = [float(sess.run(_batch())["loss"]) for _ in range(5)]
    assert losses[-1] < losses[0]


def test_gpt_seq_parallel_matches_dp():
    """Causal ring attention over a (replica x seq) mesh tracks the plain
    DP trajectory (same contract as BERT's SP test; SGD keeps reduction
    noise tight)."""
    def train(info):
        loss_fn, params, sparse = train_lib.gpt_capture(CFG, SEQ)
        ad = AutoDist(resource_spec=ResourceSpec(resource_info=info),
                      strategy_builder=AllReduce())
        sess = ad.distribute(loss_fn, params, optax.sgd(0.05),
                             sparse_vars=sparse, has_rng=True)
        b = _batch()
        losses = [float(sess.run(b)["loss"]) for _ in range(3)]
        return losses, sess.params()

    dp_info = {"nodes": [{"address": "localhost", "chips": list(range(8))}]}
    sp_info = {"nodes": [{"address": "localhost", "chips": list(range(8))}],
               "mesh": {"replica": 2, "seq": 4}}
    dp_losses, dp_params = train(dp_info)
    sp_losses, sp_params = train(sp_info)
    np.testing.assert_allclose(dp_losses, sp_losses, rtol=5e-4)
    for a, b_ in zip(jax.tree.leaves(dp_params), jax.tree.leaves(sp_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-3)


def test_gpt_uneven_batch():
    """The per-example mask composes with the per-position validity mask."""
    loss_fn, params, sparse = train_lib.gpt_capture(CFG, SEQ)
    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(8),
                  strategy_builder=AllReduce())
    sess = ad.distribute(loss_fn, params, optax.sgd(0.05),
                         sparse_vars=sparse, has_rng=True, batch_mask=True)
    b = _batch()
    uneven = {k: v[:5] for k, v in b.items()}  # 5 rows over 8 devices
    m = sess.run(uneven)
    assert np.isfinite(float(m["loss"]))


def test_generate_kv_cache_matches_full_forward():
    """Cached single-token decoding must reproduce the naive rollout that
    re-runs the full forward each step (strong KV-cache correctness)."""
    from autodist_tpu.models.gpt import generate

    model = GPT(CFG)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    prompt = np.array([[5, 17, 3], [11, 2, 9]], np.int32)
    P, NEW = prompt.shape[1], 6

    got = np.asarray(generate(CFG, params, prompt, NEW))

    # naive rollout: full forward over the sequence so far, argmax last
    seq = prompt.copy()
    forward = jax.jit(lambda p, s: model.apply({"params": p}, s))
    for _ in range(NEW):
        logits = forward(params, jnp.asarray(seq))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1), np.int32)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)

    np.testing.assert_array_equal(got, seq)


def test_generate_sampled_shapes_and_budget():
    from autodist_tpu.models.gpt import generate

    model = GPT(CFG)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    prompt = np.zeros((3, 2), np.int32)
    out = generate(CFG, params, prompt, 5, temperature=1.0,
                   rng=jax.random.PRNGKey(7))
    assert out.shape == (3, 7)
    assert (np.asarray(out) < CFG.vocab_size).all()
    import pytest

    with pytest.raises(ValueError, match="max_position"):
        generate(CFG, params, prompt, CFG.max_position)


def test_generate_shares_executable_across_prompt_lengths():
    """Prompt length is a traced scalar: same (B, total) means one compiled
    rollout regardless of P."""
    from autodist_tpu.models.decoding import _make_rollout
    from autodist_tpu.models.gpt import generate

    model = GPT(CFG)
    params = model.init(jax.random.PRNGKey(2),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    _make_rollout.cache_clear()
    a = generate(CFG, params, np.zeros((1, 2), np.int32), 3)  # total 5
    b = generate(CFG, params, np.zeros((1, 3), np.int32), 2)  # total 5
    assert a.shape == b.shape == (1, 5)
    assert _make_rollout.cache_info().currsize == 1
