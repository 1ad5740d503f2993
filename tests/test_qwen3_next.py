"""Qwen3-Next on the CPU at a small size, against the plain float32 reference
(``tests/qwen3_next_reference.py``: recurrences over positions, masked
softmax, a loop over the held experts) and against nothing else.

Everything here computes in float32 on both sides, so what differs is the
order of the sums: a chunked triangular solve against 48 rank-one updates,
packed grouped products against masked dense ones, a streamed loss against
whole logits.  That is a few float32 ulps a sum (2**-23 = 1.2e-7), grown by
the depth of the chain to some 1e-5 of the largest value: the tolerances
below are 1e-4 relative to the largest entry of each tensor.  A state of the
delta rule kept in bfloat16 (2**-9 = 2e-3 a rounding, forgotten again at the
rate the state decays) is wrong by 1.4e-3 at this size, fourteen times the
tolerance, and ``test_a_bfloat16_state_would_fail`` holds the tolerance to
that.

The rule's Pallas kernels (``ops/pallas/gated_delta.py``) run here in the
Pallas interpreter, at sizes no TPU would tile, against the same recurrence
at the same tolerance, output and every gradient; their inverse's float32
products are three bfloat16 passes here as on the chip, and the cases at the
end show that one pass, or a bfloat16 state, is out of the tolerance.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import qwen3_next_reference as R
from autodist_tpu.models import qwen3_next as Q
from autodist_tpu.models.train_lib import qwen3_next_capture
from autodist_tpu.ops.gated_delta import (chunk_gated_delta_rule,
                                          inv_unit_lower)
from autodist_tpu.ops.pallas import gated_delta as K

C = Q.QWEN3_NEXT_TINY          # hidden 64, 4 layers, 8 experts of which 4
S = 48                         # held, top-2, vocabulary 128, chunks of 16
RTOL = 1e-4

CFG = dict(
    full_attention_interval=4, num_hidden_layers=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, rope_theta=1e7,
    rms_norm_eps=1e-6, num_experts_per_tok=2, norm_topk_prob=True,
    first_expert=0)


_REFERENCE = {}     # the reference's results that two cases share


def close(got, want, rtol=RTOL):
    """Every entry within ``rtol`` of the tensor's largest."""
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    np.testing.assert_allclose(got, want, rtol=0, atol=rtol * scale)


def trees_close(got, want, rtol=RTOL):
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        try:
            close(g, flat_w[path], rtol)
        except AssertionError as e:
            raise AssertionError(jax.tree_util.keystr(path) + str(e)) from e


def capture(config):
    """``qwen3_next_capture`` with the init jitted: flax's init runs op by
    op otherwise, for longer than everything else here."""
    made = {}

    def init(key):
        made["loss_fn"], params, made["sparse"] = qwen3_next_capture(
            config, S, rng=key)
        return params

    params = jax.jit(init)(jax.random.PRNGKey(1))
    return made["loss_fn"], params, made["sparse"]


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights with every norm weight and bias-like vector moved off
    its initial value (a test that passes only at w = 0 tests nothing) and
    the matrices scaled up so that the gates and the router are not flat."""
    loss_fn, params, sparse = capture(C)
    r = np.random.RandomState(0)
    params = jax.tree.map(
        lambda x: x + 0.1 * jnp.asarray(r.randn(*x.shape), x.dtype)
        if x.ndim == 1 else x * 5, params)
    batch = {"tokens": jnp.asarray(r.randint(0, 128, (2, S)), jnp.int32),
             "targets": jnp.asarray(r.randint(0, 128, (2, S)), jnp.int32)}
    return loss_fn, params, sparse, batch


def rule_inputs(seed, s, h_k=2, h_v=4, d=8, b=2):
    r = np.random.RandomState(seed)
    q, k = (jnp.asarray(r.randn(b, s, h_k, d), jnp.float32) for _ in "qk")
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / d ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jnp.asarray(r.randn(b, s, h_v, d), jnp.float32)
    g = -jnp.asarray(r.rand(b, s, h_v) * 0.5, jnp.float32)
    beta = jnp.asarray(r.rand(b, s, h_v), jnp.float32)
    return q, k, v, g, beta


def rule_reference(q, k, v, g, beta):
    rep = v.shape[2] // q.shape[2]
    per_head = jax.vmap(R.delta_rule_recurrent, in_axes=1, out_axes=1)
    return jax.vmap(per_head)(jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2),
                              v, g, beta)


# ------------------------------------------------------ the delta rule ----

@pytest.mark.parametrize("s,chunk", [
    (41, 16),       # three chunks in one block, S not a multiple of 16
    (7, 16),        # shorter than a chunk
    (70, 4),        # eighteen chunks: two blocks, the second padded
])
def test_chunked_rule_against_the_recurrence(s, chunk):
    x = rule_inputs(s, s)
    got = jax.jit(lambda *a: chunk_gated_delta_rule(*a, chunk_size=chunk))(*x)
    close(got, jax.jit(rule_reference)(*x))


_RULE = {}         # (path, seed, s, chunk, shape, dtype) -> (o, gradients)


def rule_and_gradients(path, seed, s, chunk, dtype=jnp.float32, **shape):
    """``o`` and the gradients of ``sum(o * w)`` by ``q, k, v, g, beta``
    through one of the three paths, computed once a module."""
    key = (path, seed, s, chunk, jnp.dtype(dtype).name,
           tuple(sorted(shape.items())))
    if key not in _RULE:
        x = rule_inputs(seed, s, **shape)
        w = jnp.asarray(np.random.RandomState(4).randn(*x[2].shape),
                        jnp.float32)
        f = {"reference": rule_reference,
             "scan": functools.partial(chunk_gated_delta_rule,
                                       chunk_size=chunk, dtype=dtype),
             "kernels": functools.partial(K.gated_delta_rule,
                                          chunk_size=chunk, dtype=dtype,
                                          interpret=True)}[path]

        def run(*a):
            o, back = jax.vjp(f, *a)
            return o, back(w.astype(o.dtype))

        _RULE[key] = jax.jit(run)(*x)
    return _RULE[key]


def rules_close(got, want, rtol=RTOL):
    close(got[0], want[0], rtol)
    for g, w, name in zip(got[1], want[1], "q k v g beta".split()):
        try:
            close(g, w, rtol)
        except AssertionError as e:
            raise AssertionError("d" + name + str(e)) from e


@pytest.mark.parametrize("s,chunk", [(70, 4)])
def test_chunked_rule_gradients_against_the_recurrence(s, chunk):
    rules_close(rule_and_gradients("scan", 3, s, chunk),
                rule_and_gradients("reference", 3, s, chunk))


def test_a_bfloat16_state_would_fail():
    """The tolerance is tight enough to see the state's precision: the same
    recurrence with the state rounded to bfloat16 after every position is
    out by far more than ``RTOL``."""
    q, k, v, g, beta = (t[0] for t in rule_inputs(5, 48))
    rep = v.shape[1] // q.shape[1]

    def rounded(q, k, v, g, beta):
        def step(state, x):
            q_t, k_t, v_t, g_t, b_t = x
            state = jnp.exp(g_t) * state
            state = state + jnp.outer(k_t, b_t * (v_t - state.T @ k_t))
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
            return state, state.T @ q_t

        return jax.lax.scan(step, jnp.zeros((8, 8)), (q, k, v, g, beta))[1]

    args = (jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1), v, g, beta)
    want = jax.vmap(R.delta_rule_recurrent, in_axes=1, out_axes=1)(*args)
    bad = jax.vmap(rounded, in_axes=1, out_axes=1)(*args)
    worst = float(jnp.max(jnp.abs(bad - want)) / jnp.max(jnp.abs(want)))
    assert worst > 10 * RTOL, worst


def test_unit_lower_inverse_and_its_gradient():
    r = np.random.RandomState(6)
    a = jnp.tril(jnp.asarray(r.randn(3, 16, 16) * 0.3, jnp.float32), -1)
    eye = jnp.eye(16)
    close(inv_unit_lower(a), jnp.linalg.inv(eye + a))
    w = jnp.asarray(r.randn(3, 16, 16), jnp.float32)
    got = jax.grad(lambda a: jnp.sum(inv_unit_lower(a) * w))(a)
    want = jax.grad(lambda a: jnp.sum(
        jnp.linalg.inv(eye + jnp.tril(a, -1)) * w))(a)
    close(got, want)


# ------------------------------------------- the delta rule's kernels ----

KERNEL_CASES = [
    # S not a whole number of chunks, one block
    pytest.param(41, 16, dict(h_k=1, h_v=1, b=1), id="ragged"),
    # eighteen chunks: two blocks (the second padded), the state and its
    # cotangent carried from grid step to grid step; two value heads on
    # each of two key heads
    pytest.param(70, 4, dict(h_k=2, h_v=4, b=1), id="two-blocks"),
    # shorter than a chunk, a value head a key head, two sequences
    pytest.param(7, 16, dict(h_k=2, h_v=2, b=2), id="short")]


@pytest.mark.parametrize("s,chunk,shape", KERNEL_CASES)
def test_rule_kernels_against_the_recurrence(s, chunk, shape):
    rules_close(rule_and_gradients("kernels", 3, s, chunk, **shape),
                rule_and_gradients("reference", 3, s, chunk, **shape))


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, RTOL),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_rule_kernels_against_the_scan_form(dtype, rtol):
    """The two paths of ``chunk_gated_delta_rule`` against each other: in
    float32 at the tolerance each holds against the recurrence; with
    bfloat16 operands in their products (the benchmark's cell) to a few
    bfloat16 roundings, since the two round different intermediates."""
    shape = dict(h_k=2, h_v=4, b=1)
    got = rule_and_gradients("kernels", 3, 70, 4, dtype, **shape)
    want = rule_and_gradients("scan", 3, 70, 4, dtype, **shape)
    assert got[0].dtype == want[0].dtype == dtype
    rules_close(jax.tree.map(lambda t: t.astype(jnp.float32), got),
                jax.tree.map(lambda t: t.astype(jnp.float32), want), rtol)


@pytest.mark.parametrize("what", ["state", "inverse"])
def test_rule_kernels_with_a_bfloat16_state_or_inverse_would_fail(
        what, monkeypatch):
    """The tolerance sees the kernels' precision: with the state rounded to
    bfloat16 after every chunk, or the inverse's float32 products made in one
    bfloat16 pass instead of three, they are out of it."""
    if what == "state":
        whole = K._next_state
        monkeypatch.setattr(K, "_next_state", lambda *a: whole(*a).astype(
            jnp.bfloat16).astype(jnp.float32))
    else:
        monkeypatch.setattr(K, "_split", lambda x: (
            x.astype(jnp.bfloat16), jnp.zeros(x.shape, jnp.bfloat16)))
    shape = dict(h_k=1, h_v=1, b=1)
    x = rule_inputs(3, 41, **shape)
    w = jnp.asarray(np.random.RandomState(4).randn(*x[2].shape), jnp.float32)

    @jax.jit
    def run(*a):
        o, back = jax.vjp(functools.partial(K.gated_delta_rule, chunk_size=16,
                                            interpret=True), *a)
        return (o,) + back(w)

    want = rule_and_gradients("reference", 3, 41, 16, **shape)
    worst = max(float(jnp.max(jnp.abs(g - t)) / jnp.max(jnp.abs(t)))
                for g, t in zip(run(*x), (want[0],) + want[1]))
    assert worst > 1.5 * RTOL, worst


def test_rule_kernels_are_for_heads_of_whole_lane_tiles():
    """Who takes which path: the kernels take heads of whole 128-lane tiles
    and chunks of 64 where a block fits their VMEM; everything else, and
    every CPU run, is the scan form's (``chunk_gated_delta_rule`` asks the
    backend as the flash kernels do)."""
    assert K.tiles(64, 128, 128) and K.tiles(64, 128, 256)
    assert not K.tiles(64, 8, 8) and not K.tiles(64, 128, 64)
    assert not K.tiles(16, 128, 128) and not K.tiles(128, 128, 128)
    # the benchmark's cell, and what would not fit the backward kernel's VMEM
    assert K.tiles(64, 128, 128, rep=2, itemsize=2)
    assert not K.tiles(64, 256, 256, rep=2) and not K.tiles(64, 128, 128, 4)
    assert jax.default_backend() == "cpu"
    with jax.ensure_compile_time_eval():
        text = jax.jit(functools.partial(
            chunk_gated_delta_rule, chunk_size=64)).lower(
                *rule_inputs(3, 128, h_k=1, h_v=1, d=128, b=1)).as_text()
    assert "while" in text and "custom_call" not in text


# ------------------------------------------------------------- mixers ----

def test_gated_delta_net_against_the_reference(seeded):
    _, params, _, _ = seeded
    p = params["l_0"]["gdn"]
    x = jnp.asarray(np.random.RandomState(7).randn(2, S, 64), jnp.float32)
    got = jax.jit(lambda p, x: Q.GatedDeltaNet(C).apply({"params": p}, x))(
        p, x)
    close(got, jax.jit(jax.vmap(lambda t: R.gated_delta_net(p, t, CFG)))(x))


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_gated_attention_against_the_reference(seeded, impl):
    import dataclasses

    _, params, _, _ = seeded
    p = params["l_3"]["attn"]
    x = jnp.asarray(np.random.RandomState(8).randn(2, S, 64), jnp.float32)
    c = dataclasses.replace(C, attention_impl=impl)

    def run(f):
        return jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(f(p, x) ** 2), argnums=(0, 1)))(p, x)

    got, got_g = run(lambda p, x: Q.GatedAttention(c).apply({"params": p}, x))
    if "attn" not in _REFERENCE:
        _REFERENCE["attn"] = run(lambda p, x: jax.vmap(
            lambda t: R.gated_attention(p, t, CFG))(x))
    want, want_g = _REFERENCE["attn"]
    close(got, want)
    trees_close(got_g, want_g)


# ------------------------------------------------------- the whole model --

def test_layer_kinds_follow_the_interval():
    assert C.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert Q.Qwen3NextConfig().layer_types.count("full_attention") == 12
    assert Q.Qwen3NextConfig().layer_types[3::4] == ("full_attention",) * 12


def test_two_steps_through_distribute_against_two_reference_steps(seeded):
    """Losses, routing counters and every weight's movement: the whole
    model's loss and gradients, through the normal path."""
    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce

    import dataclasses

    _, params, sparse, _ = seeded
    loss_fn, _, _ = capture(dataclasses.replace(C, remat=True))
    r = np.random.RandomState(9)
    batches = [{"tokens": r.randint(0, 128, (2, S)).astype(np.int32),
                "targets": r.randint(0, 128, (2, S)).astype(np.int32)}
               for _ in range(2)]
    # momentum SGD and not the cell's AdamW: AdamW moves every weight by
    # about the learning rate whatever its gradient's size, so where a
    # gradient is as small as the two sides' rounding its sign, and with it
    # twice the rate, is chance.  Under SGD the weights move by the
    # gradients times the rate: a small rate (the gradients reach 10 with
    # these weights), so that the second step starts from the same place
    optimizer = optax.sgd(1e-3, momentum=0.9)
    want, want_p = R.train_steps(params, batches, CFG, optimizer)
    ad = AutoDist(resource_spec=ResourceSpec.from_num_chips(1),
                  strategy_builder=AllReduce())
    sess = ad.distribute(loss_fn, params, optimizer, has_aux=True,
                         sparse_vars=sparse)
    got = [sess.run(b) for b in batches]
    for m, w in zip(got, want):
        assert float(m["loss"]) == pytest.approx(w, rel=2e-5)
        assert float(m["moe_overflow_rows"]) == 0.0
    # the first step's counters against the reference's own count of the
    # held experts' assignments, [layers, held]
    counts = np.asarray(jax.jit(jax.vmap(
        lambda t: R.hidden_states(params, t, CFG)[1]))(
            batches[0]["tokens"])).sum(0)
    assert float(got[0]["moe_rows_here"]) == pytest.approx(
        counts.sum(1).mean())
    assert float(got[0]["moe_load_max_over_mean"]) == pytest.approx(
        (counts.max(1) / counts.mean(1)).max(), rel=1e-6)
    # what two steps moved, tensor by tensor: the gradients agree to 5e-5 of
    # each tensor's largest (the rounding of four layers), and so do the
    # sums of two of them; a gradient wrong in one place is out by its size
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want_p)[0])
    flat_0 = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    for path, got_w in jax.tree_util.tree_flatten_with_path(
            sess.state["params"])[0]:
        before, want_w = np.asarray(flat_0[path]), np.asarray(flat_w[path])
        # 5e-4 of the largest movement, and the float32 spacing of the
        # weights themselves, which a movement is read off
        atol = 5e-4 * np.abs(want_w - before).max() \
            + 2.0 ** -22 * np.abs(before).max()
        np.testing.assert_allclose(np.asarray(got_w) - before,
                                   want_w - before, rtol=0, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))
