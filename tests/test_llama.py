"""Llama-family decoder (RMSNorm + RoPE + SwiGLU + GQA): causality,
decode-cache exactness, flash-vs-XLA parity, sparse-embedding routing,
and sequence-parallel trajectory parity (rotary phases over GLOBAL
positions must line up across the seq ring)."""
import jax
import jax.numpy as jnp
import numpy as np
import optax

from autodist_tpu.autodist import AutoDist
from autodist_tpu.models import llama
from autodist_tpu.models import train_lib
from autodist_tpu.resource_spec import ResourceSpec
from autodist_tpu.strategy import AllReduce, Parallax

CFG = llama.LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, num_kv_heads=2, intermediate_size=64,
                        max_position=64, dtype=jnp.float32)
SEQ, B = 16, 8


def _batch(seed=0):
    r = np.random.RandomState(seed)
    toks = r.randint(0, CFG.vocab_size, (B, SEQ + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1], "targets": toks[:, 1:]}


def _params():
    return llama.Llama(CFG).init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, SEQ), jnp.int32))["params"]


def test_causality():
    params = _params()
    toks = _batch()["tokens"][:1]
    logits = llama.Llama(CFG).apply({"params": params}, jnp.asarray(toks))
    toks2 = np.array(toks)
    toks2[0, -1] = (toks2[0, -1] + 1) % CFG.vocab_size
    logits2 = llama.Llama(CFG).apply({"params": params}, jnp.asarray(toks2))
    np.testing.assert_allclose(logits[:, :-1], logits2[:, :-1], atol=1e-5)


def test_rope_relative_shift_invariance():
    """RoPE scores depend only on RELATIVE positions: shifting all
    positions by a constant must not change q.k phase differences."""
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(1, 8, 2, 16), jnp.float32)
    y = jnp.asarray(r.randn(1, 8, 2, 16), jnp.float32)
    p0 = jnp.arange(8)
    s0 = jnp.einsum("bqhd,bkhd->bhqk", llama.rope(x, p0), llama.rope(y, p0))
    s7 = jnp.einsum("bqhd,bkhd->bhqk", llama.rope(x, p0 + 7),
                    llama.rope(y, p0 + 7))
    np.testing.assert_allclose(s0, s7, atol=1e-4)


def test_decode_cache_matches_full_forward():
    """Greedy decode through the GQA KV cache (RoPE applied at the write
    index) must reproduce the cache-free forward exactly."""
    params = _params()
    prompt = _batch()["tokens"][:2, :4]
    out = np.asarray(llama.generate(CFG, params, prompt, 5))
    seq = np.asarray(prompt).copy()
    forward = jax.jit(lambda p, s: llama.Llama(CFG).apply({"params": p}, s))
    for _ in range(5):
        lg = forward(params, jnp.asarray(seq))
        nxt = np.asarray(jnp.argmax(lg[:, -1], axis=-1))[:, None]
        seq = np.concatenate([seq, nxt.astype(np.int32)], axis=1)
    np.testing.assert_array_equal(out, seq)


def test_flash_matches_xla():
    import dataclasses

    params = _params()
    toks = jnp.asarray(_batch()["tokens"])
    cfg_f = dataclasses.replace(CFG, attention_impl="flash")

    def loss(cfg, p):
        return llama.llama_loss(
            llama.Llama(cfg).apply({"params": p}, toks), toks)

    lx, gx = jax.jit(jax.value_and_grad(lambda p: loss(CFG, p)))(params)
    lf, gf = jax.jit(jax.value_and_grad(lambda p: loss(cfg_f, p)))(params)
    np.testing.assert_allclose(lf, lx, rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-3,
                                                         atol=1e-4), gf, gx)


def test_trains_with_sparse_embedding_routing():
    """Parallax routes the untied embedding through the sparse PS path."""
    loss_fn, params, sparse = train_lib.llama_capture(CFG, SEQ)
    assert sparse == ["embed"]
    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(8),
                  strategy_builder=Parallax())
    sess = ad.distribute(loss_fn, params, optax.adam(1e-2),
                         sparse_vars=sparse)
    losses = [float(sess.run(_batch())["loss"]) for _ in range(5)]
    assert losses[-1] < losses[0]


def test_seq_parallel_matches_dp():
    """(replica x seq) mesh: rotary phases offset to global block starts,
    K/V ring-streamed — must track the plain DP trajectory."""
    def train(info):
        loss_fn, params, sparse = train_lib.llama_capture(CFG, SEQ)
        ad = AutoDist(resource_spec=ResourceSpec(resource_info=info),
                      strategy_builder=AllReduce())
        sess = ad.distribute(loss_fn, params, optax.sgd(0.05),
                             sparse_vars=sparse)
        b = _batch()
        return [float(sess.run(b)["loss"]) for _ in range(3)]

    dp = train({"nodes": [{"address": "localhost", "chips": list(range(8))}],
                "mesh": {"replica": 8}})
    sp = train({"nodes": [{"address": "localhost", "chips": list(range(8))}],
                "mesh": {"replica": 4, "seq": 2}})
    np.testing.assert_allclose(dp, sp, atol=1e-4)
