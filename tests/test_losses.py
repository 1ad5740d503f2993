"""Streaming vocab cross-entropy (``ops/losses.py``) exactness vs the
dense-logits path — loss AND gradients (dh, dW), including bias and
valid-mask variants, plus the GPT/Llama capture integration (VERDICT r3
item 5: no dead module)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops.losses import streaming_softmax_xent

N, D, V = 24, 16, 96


def jit_grad(f, **kw):
    return jax.jit(jax.grad(f, **kw))


def jit_value_and_grad(f, **kw):
    return jax.jit(jax.value_and_grad(f, **kw))


def dense_xent(hidden, table, targets, valid=None, bias=None):
    """Reference: materialized (N, V) logits, weighted-mean NLL with the
    dense ``gpt_loss`` mask semantics (weights multiply numerator AND
    denominator)."""
    h = hidden.reshape(-1, hidden.shape[-1]).astype(jnp.float32)
    logits = h @ table.astype(jnp.float32).T
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)[None, :]
    t = targets.reshape(-1)
    w = (t >= 0).astype(jnp.float32)
    if valid is not None:
        w = w * valid.reshape(-1).astype(jnp.float32)
    safe = jnp.where(t >= 0, t, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
    return jnp.sum((lse - tl) * w) / jnp.maximum(jnp.sum(w), 1.0)


@pytest.fixture
def data():
    r = np.random.RandomState(0)
    h = jnp.asarray(r.randn(N, D), jnp.float32)
    table = jnp.asarray(r.randn(V, D) * 0.3, jnp.float32)
    t = r.randint(0, V, N)
    t[::5] = -100  # ignored positions
    return h, table, jnp.asarray(t, jnp.int32)


@pytest.mark.parametrize("chunk", [V, 32, 7, 50])  # 7/50 don't divide 96:
def test_loss_matches_dense(data, chunk):          # vocab pads + col mask
    h, table, t = data
    got = streaming_softmax_xent(h, table, t, chunk=chunk)
    want = dense_xent(h, table, t)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("chunk", [32, 50])
def test_dv_layout_matches(data, chunk):
    """(D, V) head kernels stream without a transpose copy; grads come
    back in (D, V) layout."""
    h, table, t = data
    table_dv = jnp.asarray(np.asarray(table).T)
    got = streaming_softmax_xent(h, table_dv, t, chunk=chunk, layout="dv")
    want = dense_xent(h, table, t)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    g_s = jit_grad(lambda w: streaming_softmax_xent(
        h, w, t, chunk=chunk, layout="dv"))(table_dv)
    g_d = jit_grad(lambda w: dense_xent(h, w, t))(table)
    np.testing.assert_allclose(g_s, np.asarray(g_d).T, rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("chunk", [32, 50])  # 50: padded final chunk
def test_grads_match_dense(data, chunk):
    h, table, t = data

    g_s = jit_grad(lambda hh, w: streaming_softmax_xent(hh, w, t,
                                                        chunk=chunk),
                   argnums=(0, 1))(h, table)
    g_d = jit_grad(lambda hh, w: dense_xent(hh, w, t),
                   argnums=(0, 1))(h, table)
    np.testing.assert_allclose(g_s[0], g_d[0], rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(g_s[1], g_d[1], rtol=2e-5, atol=1e-6)


def test_bias_variant(data):
    h, table, t = data
    bias = jnp.asarray(np.random.RandomState(1).randn(V), jnp.float32)
    got = streaming_softmax_xent(h, table, t, bias=bias, chunk=32)
    want = dense_xent(h, table, t, bias=bias)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    g_s = jit_grad(lambda hh: streaming_softmax_xent(
        hh, table, t, bias=bias, chunk=32))(h)
    g_d = jit_grad(lambda hh: dense_xent(hh, table, t, bias=bias))(h)
    np.testing.assert_allclose(g_s, g_d, rtol=2e-5, atol=1e-6)


def test_valid_mask(data):
    h, table, t = data
    valid = jnp.asarray(np.random.RandomState(2).randint(0, 2, N),
                        jnp.float32)
    got = streaming_softmax_xent(h, table, t, valid=valid, chunk=32)
    want = dense_xent(h, table, t, valid=valid)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_nonbinary_weights_match_dense(data):
    """Fractional mask values weight the mean (numerator AND denominator)
    — the dense gpt_loss semantics (the capture-level test below pins the
    full-path agreement through _positional_mask)."""
    h, table, t = data
    w = jnp.asarray(np.random.RandomState(3).rand(N), jnp.float32)
    got = streaming_softmax_xent(h, table, t, valid=w, chunk=32)
    want = dense_xent(h, table, t, valid=w)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_all_masked_is_finite(data):
    h, table, _ = data
    t = jnp.full((N,), -100, jnp.int32)
    got = streaming_softmax_xent(h, table, t, chunk=32)
    assert np.isfinite(float(got)) and float(got) == 0.0


def test_bf16_hidden(data):
    """bf16 activations (the models' dtype) still accumulate in f32."""
    h, table, t = data
    got = streaming_softmax_xent(h.astype(jnp.bfloat16), table, t, chunk=32)
    want = dense_xent(h.astype(jnp.bfloat16).astype(jnp.float32), table, t)
    np.testing.assert_allclose(got, want, rtol=1e-5)


# ------------------------------------------------- capture integration --

def _batch(r, B, S, vocab):
    toks = r.randint(0, vocab, (B, S))
    tgt = np.roll(toks, -1, axis=1)
    tgt[:, -1] = -100
    return {"tokens": jnp.asarray(toks, jnp.int32),
            "targets": jnp.asarray(tgt, jnp.int32)}


def test_gpt_capture_streaming_matches_dense():
    from autodist_tpu.models import train_lib
    from autodist_tpu.models.gpt import GPT_TINY

    r = np.random.RandomState(0)
    batch = _batch(r, 2, 16, GPT_TINY.vocab_size)
    rng = jax.random.PRNGKey(0)
    loss_d, params, _ = train_lib.gpt_capture(GPT_TINY, 16)
    loss_s, params_s, _ = train_lib.gpt_capture(GPT_TINY, 16,
                                                streaming_loss=True,
                                                loss_chunk=128)
    chex = jax.tree_util.tree_structure(params)
    assert chex == jax.tree_util.tree_structure(params_s)

    ld, gd = jit_value_and_grad(loss_d)(params, batch, rng)
    ls, gs = jit_value_and_grad(loss_s)(params, batch, rng)
    np.testing.assert_allclose(ld, ls, rtol=1e-5)
    for (kd, vd), (ks, vs) in zip(
            jax.tree_util.tree_leaves_with_path(gd),
            jax.tree_util.tree_leaves_with_path(gs)):
        assert kd == ks
        np.testing.assert_allclose(vd, vs, rtol=5e-4, atol=2e-5,
                                   err_msg=str(kd))


def test_llama_capture_streaming_matches_dense():
    from autodist_tpu.models import train_lib
    from autodist_tpu.models.llama import LLAMA_TINY

    r = np.random.RandomState(1)
    batch = _batch(r, 2, 16, LLAMA_TINY.vocab_size)
    loss_d, params, _ = train_lib.llama_capture(LLAMA_TINY, 16)
    loss_s, _, _ = train_lib.llama_capture(LLAMA_TINY, 16,
                                           streaming_loss=True,
                                           loss_chunk=64)
    ld, gd = jit_value_and_grad(loss_d)(params, batch)
    ls, gs = jit_value_and_grad(loss_s)(params, batch)
    np.testing.assert_allclose(ld, ls, rtol=1e-5)
    for (kd, vd), (ks, vs) in zip(
            jax.tree_util.tree_leaves_with_path(gd),
            jax.tree_util.tree_leaves_with_path(gs)):
        assert kd == ks
        np.testing.assert_allclose(vd, vs, rtol=5e-4, atol=2e-5,
                                   err_msg=str(kd))


def test_gpt_capture_streaming_with_session_mask():
    """The session's per-example uneven-batch mask flows through the
    streaming path with the same semantics as the dense gpt_loss."""
    from autodist_tpu.const import BATCH_MASK_KEY
    from autodist_tpu.models import train_lib
    from autodist_tpu.models.gpt import GPT_TINY

    r = np.random.RandomState(2)
    batch = _batch(r, 4, 16, GPT_TINY.vocab_size)
    # non-binary weights: the streaming path must weight the mean exactly
    # like the dense gpt_loss (numerator and denominator)
    batch[BATCH_MASK_KEY] = jnp.asarray([1.0, 0.5, 0.25, 0.0])
    rng = jax.random.PRNGKey(0)
    loss_d, params, _ = train_lib.gpt_capture(GPT_TINY, 16)
    loss_s, _, _ = train_lib.gpt_capture(GPT_TINY, 16, streaming_loss=True,
                                         loss_chunk=128)
    np.testing.assert_allclose(loss_d(params, batch, rng),
                               loss_s(params, batch, rng), rtol=1e-5)
