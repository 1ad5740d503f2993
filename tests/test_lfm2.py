"""LFM2 on the CPU at a small size, against the plain float32 reference
(``tests/lfm2_reference.py``: the convolution as shifted sums, masked softmax
one head after another, a loop over the held experts) and against nothing
else.

Everything here computes in float32 on both sides, so what differs is the
order of the sums: a projection multiplied in three parts against one
product, packed grouped products and a scatter-add against masked dense
ones, a streamed loss over the tied embedding against whole logits.  That is
a few float32 ulps a sum (2**-23 = 1.2e-7), grown by the depth of the chain
to some 1e-5 of the largest value: the tolerances below are 1e-4 relative to
the largest entry of each tensor.  ``test_a_departure_would_fail`` holds that
tolerance to the four mistakes it has to see.  Three are out by more than
ten times it: a router that forgets its selection bias (other experts
chosen), q and k turned by the rotary before they are normed (the norm's
weight then scales other entries), a convolution with a tap missing.  The
fourth, a router whose outputs are rounded to bfloat16 (2**-9 of a logit
moves every weight, and at a near tie the choice), reads 2.4e-4 to 5e-4 over
six draws at this size where the program reads 3e-7: out by more than twice
the tolerance.
"""
import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import lfm2_reference as R
from autodist_tpu.models import lfm2 as L
from autodist_tpu.models.train_lib import lfm2_capture

C = L.LFM2_TINY                # hidden 64; published layers 0, 2, 3 of five:
S = 48                         # conv + dense, attention + routed, conv +
RTOL = 1e-4                    # routed; 8 experts of which 4 held, top-2

CFG = dict(
    layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
    num_attention_heads=4, num_key_value_heads=2, norm_eps=1e-5,
    rope_theta=1e6, num_experts_per_tok=2, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1.0, first_expert=0)


def close(got, want, rtol=RTOL):
    """Every entry within ``rtol`` of the tensor's largest."""
    got, want = np.asarray(got), np.asarray(want)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    np.testing.assert_allclose(got, want, rtol=0, atol=rtol * scale)


def worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def trees_close(got, want, rtol=RTOL):
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        try:
            close(g, flat_w[path], rtol)
        except AssertionError as e:
            raise AssertionError(jax.tree_util.keystr(path) + str(e)) from e


def capture(config):
    """``lfm2_capture`` with the init jitted (flax's init runs op by op
    otherwise)."""
    made = {}

    def init(key):
        made["loss_fn"], params, made["sparse"] = lfm2_capture(
            config, S, rng=key)
        return params

    params = jax.jit(init)(jax.random.PRNGKey(1))
    return made["loss_fn"], params, made["sparse"]


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights with every norm weight and bias-like vector moved off
    its initial value (the selection bias, which starts at zero, and the q
    and k norms' weights, which start at one, among them: a test that passes
    only there tests nothing) and the matrices scaled up so that the gates
    and the router are not flat."""
    loss_fn, params, sparse = capture(C)
    r = np.random.RandomState(0)
    params = jax.tree.map(
        lambda x: x + 0.1 * jnp.asarray(r.randn(*x.shape), x.dtype)
        if x.ndim == 1 else x * 5, params)
    return loss_fn, params, sparse


def hidden(seed):
    return jnp.asarray(np.random.RandomState(seed).randn(2, S, 64),
                       jnp.float32)


def value_and_grads(f, p, x):
    return jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(f(p, x) ** 2), argnums=(0, 1)))(p, x)


def routed(p, x):
    return L.RoutedFFN(C).apply({"params": p}, x)


def routed_reference(p, x):
    y, counts = R.routed_feed_forward(p, x.reshape(-1, x.shape[-1]), CFG)
    return y.reshape(x.shape), counts


# (the program's module, the reference's function a sequence or a batch of
# tokens, where its weights lie in the tiny model's tree)
PARTS = {
    "short_conv": (L.ShortConv(C), jax.vmap(R.short_conv, (None, 0)),
                   ("l_0", "sconv")),
    "attention_xla": (L.Attention(C), jax.vmap(
        lambda p, t: R.attention(p, t, CFG), (None, 0)), ("l_1", "attn")),
    "attention_flash": (L.Attention(dataclasses.replace(
        C, attention_impl="flash")), jax.vmap(
        lambda p, t: R.attention(p, t, CFG), (None, 0)), ("l_1", "attn")),
    "dense_ffn": (L.DenseFFN(C), lambda p, x: R.swiglu(
        x, p["gate"], p["up"], p["down"]), ("l_0", "ffn")),
    "routed_ffn": (None, lambda p, x: routed_reference(p, x)[0],
                   ("l_1", "moe")),
}


@pytest.mark.parametrize("part", sorted(PARTS))
def test_each_mixer_and_feed_forward_against_the_reference(seeded, part):
    """Outputs and the gradients of every weight and of the input."""
    module, reference, (layer, name) = PARTS[part]
    p, x = seeded[1][layer][name], hidden(7)
    mine = (lambda p, x: routed(p, x)[0]) if module is None else \
        (lambda p, x: module.apply({"params": p}, x))
    got, got_g = value_and_grads(mine, p, x)
    want, want_g = value_and_grads(reference, p, x)
    close(got, want)
    trees_close(got_g, want_g)


def test_routing_counters_and_a_bias_without_a_gradient(seeded):
    p, x = seeded[1]["l_2"]["moe"], hidden(9)
    g = jax.jit(jax.grad(lambda p, x: jnp.sum(routed(p, x)[0] ** 2)))(p, x)
    assert not np.any(np.asarray(g["expert_bias"]))
    assert np.any(np.asarray(g["router"]))
    stats = np.asarray(jax.jit(lambda p, x: routed(p, x)[1])(p, x))
    counts = np.asarray(jax.jit(lambda p, x: routed_reference(p, x)[1])(p, x))
    assert stats[0] == counts.sum() and stats[2] == 0
    assert stats[1] == pytest.approx(counts.max() / counts.mean())


def _rotary_first(p, x):
    """``R.attention`` with q and k turned before they are normed."""
    rms, rotary = R.rms, R.rotary
    with mock.patch.object(
            R, "rms", lambda t, w, eps: rms(rotary(t, 1e6), w, eps)), \
            mock.patch.object(R, "rotary", lambda t, theta: t):
        return jax.vmap(lambda t: R.attention(p, t, CFG))(x)


def _bfloat16_router(p, x):
    """``R.routed_feed_forward`` with the router's outputs rounded to
    bfloat16 before the sigmoid."""
    sigmoid = jax.nn.sigmoid
    with mock.patch.object(jax.nn, "sigmoid", lambda z: sigmoid(
            z.astype(jnp.bfloat16).astype(jnp.float32))):
        return routed_reference(p, x)[0]


# (weights, the program, the reference gone wrong, how many times RTOL at
# least it is out by)
DEPARTURES = {
    "a_dropped_selection_bias": (
        ("l_1", "moe"), lambda p, x: routed(p, x)[0],
        lambda p, x: routed_reference(
            {**p, "expert_bias": jnp.zeros_like(p["expert_bias"])}, x)[0],
        10),
    "rotary_before_the_norm": (
        ("l_1", "attn"),
        lambda p, x: L.Attention(C).apply({"params": p}, x), _rotary_first,
        10),
    "a_missing_tap": (
        ("l_0", "sconv"),
        lambda p, x: L.ShortConv(C).apply({"params": p}, x),
        lambda p, x: jax.vmap(R.short_conv, (None, 0))(
            {**p, "conv": p["conv"].at[0].set(0.0)}, x), 10),
    "a_bfloat16_router": (
        ("l_1", "moe"), lambda p, x: routed(p, x)[0], _bfloat16_router, 2),
}


@pytest.mark.parametrize("what", sorted(DEPARTURES))
def test_a_departure_would_fail(seeded, what):
    (layer, name), mine, wrong, times = DEPARTURES[what]
    p, x = seeded[1][layer][name], hidden(9)
    bad = worst(jax.jit(mine)(p, x), jax.jit(wrong)(p, x))
    assert bad > times * RTOL, bad


# ------------------------------------------------------- the whole model --

@pytest.mark.parametrize("layers_here,dense,want", [
    (None, 2, [("conv", "dense"), ("conv", "dense"),
               ("full_attention", "moe"), ("conv", "moe"), ("conv", "moe")]),
    ((0, 2, 3), 2, [("conv", "dense"), ("full_attention", "moe"),
                    ("conv", "moe")]),
    ((2, 4), 0, [("full_attention", "moe"), ("conv", "moe")]),
])
def test_layer_kinds_follow_layer_types_and_num_dense_layers(
        layers_here, dense, want):
    c = dataclasses.replace(C, layers_here=layers_here,
                            num_dense_layers=dense)
    assert list(c.layer_kinds) == want


def test_the_published_layer_kinds_and_what_is_refused():
    kinds = L.Lfm2Config().layer_kinds
    assert len(kinds) == 24
    assert [i for i, (m, _) in enumerate(kinds) if m == "full_attention"] \
        == [2, 6, 10, 14, 18, 21]
    assert [ff for _, ff in kinds] == ["dense"] * 2 + ["moe"] * 22
    # the benchmark's cut: one dense layer, then one whole period
    cut = dataclasses.replace(L.Lfm2Config(), layers_here=(0, 2, 3, 4, 5))
    assert cut.layer_kinds == (
        ("conv", "dense"), ("full_attention", "moe"), ("conv", "moe"),
        ("conv", "moe"), ("conv", "moe"))
    with pytest.raises(ValueError, match="none of"):
        dataclasses.replace(C, layer_types=("conv", "window")).layer_kinds
    with pytest.raises(ValueError, match="no routed layer"):
        capture(dataclasses.replace(C, layers_here=(0, 1)))


def test_loss_and_gradients_and_the_tied_head(seeded):
    """The whole model's loss and every gradient against the reference's;
    the embedding's gradient holds the head's part: rows of ids that no
    input position holds get a gradient all the same."""
    loss_fn, params, _ = seeded
    r = np.random.RandomState(3)
    batch = {"tokens": jnp.asarray(r.randint(0, 64, (2, S)), jnp.int32),
             "targets": jnp.asarray(r.randint(0, 128, (2, S)), jnp.int32)}
    (got, aux), got_g = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
        params, batch)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p, b: R.loss(p, b, CFG)))(params, batch)
    assert float(got) == pytest.approx(float(want), rel=2e-5)
    trees_close(got_g, want_g)
    assert "lm_head" not in params
    assert float(jnp.max(jnp.abs(got_g["embed"][64:]))) > 0
    assert float(aux["moe_overflow_rows"]) == 0


def test_the_configuration_file_counts_its_parameters():
    """The benchmark's configuration, built as its family builds it: the
    count of the real parameter tree is the one the file states, and the
    issue's arithmetic."""
    from benchmark.harness import cells

    cell, config = cells.load_cell("lfm2_8b_a1b.train_fed")
    cfg = cells.load_family(config["family"]).model_config(config, cell)
    assert cfg.layers_here == (0, 2, 3, 4, 5)
    params = jax.eval_shape(
        lambda key: lfm2_capture(cfg, 16, rng=key)[1], jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    d = 2048
    sconv = d * 3 * d + 3 * d + d * d
    attn = 2 * d * d + 2 * d * 512 + 2 * 64
    dense = 3 * d * 7168
    routed_layer = d * 32 + 32 + 8 * 3 * d * 1792
    assert n == config["parameters"] == 507820288 \
        == 4 * sconv + attn + dense + 4 * routed_layer + 5 * 2 * d + d \
        + 16384 * d


def test_two_steps_through_distribute_against_two_reference_steps(seeded):
    """Losses, routing counters and every weight's movement: the whole
    model's loss and gradients, through the normal path."""
    from autodist_tpu.autodist import AutoDist
    from autodist_tpu.resource_spec import ResourceSpec
    from autodist_tpu.strategy import AllReduce

    _, params, sparse = seeded
    loss_fn, _, _ = capture(dataclasses.replace(C, remat=True))
    r = np.random.RandomState(9)
    batches = [{"tokens": r.randint(0, 128, (2, S)).astype(np.int32),
                "targets": r.randint(0, 128, (2, S)).astype(np.int32)}
               for _ in range(2)]
    # momentum SGD and not the cell's AdamW, at a small rate: see
    # tests/test_qwen3_next.py
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
    # held experts' assignments, [routed layers, held]
    counts = np.asarray(jax.jit(jax.vmap(
        lambda t: R.hidden_states(params, t, CFG)[1]))(
            batches[0]["tokens"])).sum(0)
    assert counts.shape == (2, 4)
    assert float(got[0]["moe_rows_here"]) == pytest.approx(
        counts.sum(1).mean())
    assert float(got[0]["moe_load_max_over_mean"]) == pytest.approx(
        (counts.max(1) / counts.mean(1)).max(), rel=1e-6)
    # what two steps moved, tensor by tensor (tests/test_qwen3_next.py has
    # the reasons for the two terms); the selection bias does not move
    flat_w = dict(jax.tree_util.tree_flatten_with_path(want_p)[0])
    flat_0 = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    for path, got_w in jax.tree_util.tree_flatten_with_path(
            sess.state["params"])[0]:
        before, want_w = np.asarray(flat_0[path]), np.asarray(flat_w[path])
        atol = 5e-4 * np.abs(want_w - before).max() \
            + 2.0 ** -22 * np.abs(before).max()
        np.testing.assert_allclose(np.asarray(got_w) - before,
                                   want_w - before, rtol=0, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))
        if "expert_bias" in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(np.asarray(got_w), before)
