"""The expert layer (``parallel/moe.py``) against the plain references' routed
feed-forwards (a loop over the held experts with a mask), on one device and
over an ``expert`` mesh axis, under the combinations of scoring rule and
expert body that the models bring: a softmax over the experts with SwiGLU
experts and a gated shared expert (``tests/qwen3_next_reference.py``), a
sigmoid an expert with a selection bias, scaled weights, ``relu(.)^2``
experts of two matrices and an ungated shared expert
(``tests/nemotron_h_reference.py``), and the sigmoid rule at scale 1 round
SwiGLU experts with no shared expert (``tests/lfm2_reference.py``).

float32 on both sides; what differs is the order of the sums (packed grouped
products and a scatter-add against masked dense products), so 1e-5 of the
largest entry.  The cases of the top-1 capacity layer this file used to test
are all here in the new layer's terms: the expert-parallel result against the
dense one (tokens replicated and tokens sharded over the axis, now both
exact), and what happens past the capacity (nothing is dropped silently: a
count, and a non-finite loss).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lfm2_reference as RL
import nemotron_h_reference as RN
import qwen3_next_reference as R
from autodist_tpu.models import lfm2 as L
from autodist_tpu.models import nemotron_h as N
from autodist_tpu.models import qwen3_next as Q
from autodist_tpu.parallel.mesh import build_mesh
from autodist_tpu.parallel.moe import (expert_layer, pack_held, route,
                                       top_k_of)

E, D, F, T, K = 8, 16, 32, 64, 2
SCALE = 2.5
RULES = ["softmax_swiglu", "sigmoid_relu2", "sigmoid_swiglu"]
# the sigmoid rules' selection bias by the name each model gives it, with the
# scale and the epsilon of the normalisation
SIGMOID = {"router_bias": dict(scale=SCALE, norm_eps=1e-20),
           "expert_bias": dict(scale=1.0, norm_eps=1e-6)}
BIAS_OF = {"sigmoid_relu2": "router_bias", "sigmoid_swiglu": "expert_bias"}


def weights(seed=0, experts=E, rule="softmax_swiglu"):
    r = np.random.RandomState(seed)

    def w(*shape, scale):
        return jnp.asarray(r.randn(*shape) * scale, jnp.float32)

    p = {"router": w(D, E, scale=0.5),
         "gate": w(experts, D, F, scale=0.3),
         "up": w(experts, D, F, scale=0.3),
         "down": w(experts, F, D, scale=0.3),
         "shared_gate": w(D, F, scale=0.3), "shared_up": w(D, F, scale=0.3),
         "shared_down": w(F, D, scale=0.3),
         "shared_router": w(D, 1, scale=0.5)}
    # a bias the size of the scores' spread: it changes the choice
    if rule == "sigmoid_relu2":
        p = {k: v for k, v in p.items()
             if k not in ("gate", "shared_gate", "shared_router")}
        p["router_bias"] = w(E, scale=0.2)
    elif rule == "sigmoid_swiglu":
        p = {k: v for k, v in p.items() if not k.startswith("shared")}
        p["expert_bias"] = w(E, scale=0.2)
    return p


def bias_of(p):
    """The name of the selection bias in ``p``, or ``None``."""
    return next((k for k in SIGMOID if k in p), None)


def tokens(seed=1, t=T):
    return jnp.asarray(np.random.RandomState(seed).randn(t, D), jnp.float32)


def reference(p, x, first=0):
    """``(routed part, shared part, counts)`` of the reference's layer, the
    rule read off the weights."""
    if "expert_bias" in p:
        cfg = {"num_experts_per_tok": K, "first_expert": first}
        whole, counts = RL.routed_feed_forward(p, x, cfg)
        shared = jnp.zeros_like(whole)
    elif "router_bias" in p:
        cfg = {"num_experts_per_tok": K, "first_expert": first,
               "routed_scaling_factor": SCALE}
        whole, counts = RN.routed_feed_forward(p, x, cfg)
        shared = RN.expert(x, p["shared_up"], p["shared_down"])
    else:
        cfg = {"num_experts_per_tok": K, "first_expert": first}
        whole, counts = R.routed_feed_forward(p, x, cfg)
        shared = jax.nn.sigmoid(x @ p["shared_router"]) * R.swiglu(
            x, p["shared_gate"], p["shared_up"], p["shared_down"])
    return whole - shared, shared, counts


def layer(p, x, **kw):
    bias = bias_of(p)
    if bias:
        kw = dict(score=jax.nn.sigmoid, select_bias=p[bias], **SIGMOID[bias],
                  **kw)
    if "gate" not in p:
        kw["activation"] = N.relu2
    return expert_layer(x, p["router"], p.get("gate"), p["up"], p["down"],
                        top_k=K, **kw)


def share(p, lo, hi):
    return {**p, **{k: p[k][lo:hi] for k in ("gate", "up", "down")
                    if k in p}}


def close(got, want, rtol=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=rtol * max(np.abs(want).max(), 1e-30))


# --------------------------------------------------------- one device ----

@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("first,held", [(0, 8), (4, 4), (6, 2)])
def test_expert_layer_against_the_reference(first, held, rule):
    p, x = share(weights(rule=rule), first, first + held), tokens()

    def run(f):
        return jax.jit(jax.value_and_grad(
            lambda p, x: jnp.sum(f(p, x) ** 2), argnums=(0, 1)))(p, x)

    got, (gp, gx) = run(lambda p, x: layer(p, x, first_expert=first)[0])
    want, (wp, wx) = run(lambda p, x: reference(p, x, first)[0])
    close(got, want)
    close(gx, wx)
    for k in set(p) & {"router", "gate", "up", "down"}:
        close(gp[k], wp[k])
    if bias_of(p):                  # only the choice reads it
        assert not np.any(np.asarray(gp[bias_of(p)]))
    stats = jax.jit(lambda p, x: layer(p, x, first_expert=first)[1])(p, x)
    counts = np.asarray(jax.jit(lambda p, x: reference(p, x, first)[2])(p, x))
    assert float(stats["rows_here"]) == counts.sum()
    assert float(stats["load_max_over_mean"]) == pytest.approx(
        counts.max() / counts.mean())
    assert float(stats["overflow_rows"]) == 0


def _qwen3_next_shared(p, flat):
    return jax.nn.sigmoid(flat @ p["shared_router"]) * R.swiglu(
        flat, p["shared_gate"], p["shared_up"], p["shared_down"])


# (the model's routed feed-forward, its configuration uncut, the reference's
# layer, the part every chip computes alike, experts a share)
SHARES = {
    "qwen3_next_two_of_four": (
        Q.SparseMoE, dataclasses.replace(Q.QWEN3_NEXT_TINY,
                                         experts_held=None),
        R.routed_feed_forward, _qwen3_next_shared, 4),
    "nemotron_h_sixteen_of_eight": (
        N.RoutedFFN, dataclasses.replace(
            N.NEMOTRON_H_TINY, n_routed_experts=128, num_experts_per_tok=6,
            experts_held=None),
        RN.routed_feed_forward,
        lambda p, flat: RN.expert(flat, p["shared_up"], p["shared_down"]), 8),
    "lfm2_four_of_eight": (
        L.RoutedFFN, dataclasses.replace(
            L.LFM2_TINY, num_experts=32, num_experts_per_tok=4,
            experts_held=None),
        RL.routed_feed_forward, None, 8),
}


@pytest.mark.parametrize("case", sorted(SHARES))
def test_the_shares_add_up_to_the_uncut_layer(case):
    """The share test at each model's cut (Qwen3-Next's halves; Nemotron-H's
    16 shares of 8 of 128 experts, six a token; LFM2's 4 shares of 8 of 32,
    four a token): the parts that all the shares give, with what every chip
    computes alike (a shared expert; LFM2 has none) counted once, are what
    the uncut reference gives for the whole layer.  Every share scores all
    the experts, under a seeded selection bias where the rule has one."""
    module, c, reference, shared_of, held = SHARES[case]
    x = jnp.asarray(np.random.RandomState(3).randn(2, 24, 64), jnp.float32)
    whole = module(c)
    p = jax.jit(whole.init)(jax.random.PRNGKey(0), x)["params"]
    p = jax.tree.map(lambda w: w * 8, p)            # a router with opinions
    if bias_of(p):
        p[bias_of(p)] = 0.2 * jnp.asarray(
            np.random.RandomState(4).randn(*p[bias_of(p)].shape), jnp.float32)
    k = c.num_experts_per_tok
    cfg = {"num_experts_per_tok": k, "routed_scaling_factor": getattr(
        c, "routed_scaling_factor", 1.0)}
    want = jax.jit(jax.vmap(lambda t: reference(p, t, cfg)[0]))(x)
    experts = p["up"].shape[0]
    shared = 0.0 if shared_of is None else shared_of(
        p, x.reshape(-1, 64)).reshape(x.shape)
    total, rows = (1 - experts // held) * shared, 0
    for first in range(0, experts, held):
        part = dataclasses.replace(c, experts_held=held, first_expert=first)
        y, stats = jax.jit(module(part).apply)(
            {"params": share(p, first, first + held)}, x)
        total = total + y
        rows += float(stats[0])
    close(total, want)
    close(jax.jit(whole.apply)({"params": p}, x)[0], want)
    # every assignment lands on exactly one of the shares
    assert rows == x.shape[0] * x.shape[1] * k


def test_top_k_by_argmax_is_lax_top_k():
    r = np.random.RandomState(4)
    p = jnp.asarray(r.rand(50, 16), jnp.float32)
    p = p.at[:, 5].set(p[:, 2])                     # ties: lower index first
    p = p.at[7].set(0.25)                           # a whole row of ties
    want_v, want_i = jax.lax.top_k(p, 4)
    got_v, got_i = top_k_of(p, 4)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))
    w = jnp.asarray(r.randn(50, 4), jnp.float32)
    close(jax.grad(lambda p: jnp.sum(top_k_of(p, 4)[0] * w))(p),
          jax.grad(lambda p: jnp.sum(jax.lax.top_k(p, 4)[0] * w))(p))


def test_routing_weights_are_normalised_over_all_the_chosen():
    p, x = weights(), tokens()
    idx, w = route(x, p["router"], K)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-6)
    probs = jax.nn.softmax(x @ p["router"], -1)
    _, raw = route(x, p["router"], K, norm_topk=False)
    close(raw, jnp.take_along_axis(probs, idx, -1))
    assert np.all(np.asarray(idx[:, 0]) != np.asarray(idx[:, 1]))


@pytest.mark.parametrize("rule", sorted(BIAS_OF))
def test_sigmoid_scores_are_normalised_and_scaled(rule):
    p, x = weights(rule=rule), tokens()
    scale, eps = (SIGMOID[BIAS_OF[rule]][k] for k in ("scale", "norm_eps"))
    rule = dict(score=jax.nn.sigmoid, norm_eps=eps)
    idx, w = route(x, p["router"], K, scale=scale, **rule)
    # an epsilon of 1e-6 beside a sum of two sigmoids shows in the sixth digit
    np.testing.assert_allclose(np.asarray(w.sum(-1)), scale, rtol=1e-5)
    scores = jax.nn.sigmoid(x @ p["router"])
    _, raw = route(x, p["router"], K, norm_topk=False, **rule)
    close(raw, jnp.take_along_axis(scores, idx, -1))
    # every expert on its own: the scores of a row do not add up to one
    assert float(jnp.max(jnp.abs(scores.sum(-1) - 1.0))) > 0.5


@pytest.mark.parametrize("rule", sorted(BIAS_OF))
def test_the_selection_bias_changes_who_is_chosen_and_not_the_weights(rule):
    p, x = weights(rule=rule), tokens()
    bias = p[BIAS_OF[rule]]
    scale, eps = (SIGMOID[BIAS_OF[rule]][k] for k in ("scale", "norm_eps"))
    rule = dict(score=jax.nn.sigmoid, scale=scale, norm_eps=eps)
    plain_i, plain_w = route(x, p["router"], K, **rule)
    idx, w = route(x, p["router"], K, select_bias=bias, **rule)
    scores = jax.nn.sigmoid(x @ p["router"])
    _, want_i = jax.lax.top_k(scores + bias, K)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_i))
    moved = np.any(np.asarray(idx) != np.asarray(plain_i), axis=-1)
    assert 0 < moved.sum() < T
    # the weights are the chosen experts' scores as they are, without the
    # bias: where the choice is the same so are they, to the last bit
    chosen = jnp.take_along_axis(scores, idx, -1)
    close(w, scale * chosen / (chosen.sum(-1, keepdims=True) + eps))
    np.testing.assert_array_equal(np.asarray(w)[~moved],
                                  np.asarray(plain_w)[~moved])
    # and no gradient reaches it
    g = jax.grad(lambda b: jnp.sum(route(
        x, p["router"], K, select_bias=b, **rule)[1] ** 2))(bias)
    assert not np.any(np.asarray(g))


@pytest.mark.parametrize("rows_bound", [200, 20, 7])
def test_packed_rows_are_sorted_by_expert_then_token(rows_bound):
    idx = jnp.asarray(np.random.RandomState(5).randint(0, 8, (40, 3)),
                      jnp.int32)
    flat, sizes, counts = (np.asarray(a) for a in pack_held(
        idx, 2, 4, rows_bound))
    ids = np.asarray(idx).reshape(-1)
    held = [i for e in range(2, 6) for i in np.flatnonzero(ids == e)]
    n = min(len(held), rows_bound)
    assert list(flat[:n]) == held[:n]
    assert np.all(flat[n:] == ids.size)
    assert list(counts) == [np.sum(ids == e) for e in range(2, 6)]
    assert sizes.sum() == n and np.all(sizes <= counts)
    assert list(np.repeat(np.arange(4), sizes)) == list(ids[flat[:n]] - 2)


# ------------------------------------------- past the bound of the rows ----

@pytest.mark.parametrize("rule", RULES)
def test_overflow_is_counted_and_the_kept_rows_are_right(rule):
    """What the old layer's capacity dropped in silence: assignments past
    ``rows_bound`` are counted, and what is computed is exactly the
    assignments that fit (the first experts' rows)."""
    p, x = weights(rule=rule), tokens()
    full, stats = jax.jit(layer)(p, x)
    counts = np.asarray(jax.jit(lambda p, x: reference(p, x)[2])(p, x))
    bound = int(counts[:3].sum())                   # room for three experts
    cut, cut_stats = jax.jit(
        lambda p, x: layer(p, x, rows_bound=bound))(p, x)
    assert float(cut_stats["overflow_rows"]) == counts.sum() - bound
    assert float(cut_stats["rows_here"]) == float(stats["rows_here"])
    three = share(p, 0, 3)
    close(cut, jax.jit(layer)(three, x)[0])
    assert not np.allclose(np.asarray(cut), np.asarray(full))


@pytest.mark.parametrize("rule", RULES)
def test_overflow_makes_the_loss_non_finite(rule):
    from autodist_tpu.models import train_lib

    s = 24
    batch = {"tokens": jnp.zeros((2, s), jnp.int32),
             "targets": jnp.ones((2, s), jnp.int32)}
    capture, tiny = {
        "softmax_swiglu": (train_lib.qwen3_next_capture, dataclasses.replace(
            Q.QWEN3_NEXT_TINY, num_layers=1, full_attention_interval=1)),
        "sigmoid_relu2": (train_lib.nemotron_h_capture, dataclasses.replace(
            N.NEMOTRON_H_TINY, pattern="E")),
        "sigmoid_swiglu": (train_lib.lfm2_capture, dataclasses.replace(
            L.LFM2_TINY, layers_here=(3,)))}[rule]
    for bound, finite in ((None, True), (8, False)):
        c = dataclasses.replace(tiny, rows_bound=bound)
        made = {}

        def init(key):
            made["loss_fn"], params, _ = capture(c, s, rng=key)
            return params

        params = jax.jit(init)(jax.random.PRNGKey(0))
        loss, aux = jax.jit(made["loss_fn"])(params, batch)
        assert bool(np.isfinite(float(loss))) is finite
        assert (float(aux["moe_overflow_rows"]) == 0) is finite
        assert float(aux["moe_rows_here"]) > 8


# ------------------------------------------------ through the kernels ----

@pytest.mark.parametrize("rule", RULES)
def test_the_kernels_path_is_the_ragged_dot_path(rule, monkeypatch):
    """``expert_layer`` as a TPU runs it (the grouped products through the
    Pallas kernels of ``ops/pallas/grouped_matmul.py``, here in the Pallas
    interpreter) against the ``ragged_dot`` path: ``y``, ``stats`` and every
    gradient, with room for three times the rows that are there, and what
    lies past them (NaN by the kernels' leave) reaching nothing."""
    from autodist_tpu.ops.pallas import flash_attention
    from autodist_tpu.ops.pallas import grouped_matmul as G
    from autodist_tpu.parallel import moe

    p, x = weights(rule=rule), tokens()
    bound = 3 * T * K

    def run():
        def loss(p, x):
            y, stats = layer(p, x, rows_bound=bound)
            return jnp.sum(y ** 2), (y, stats)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True))(p, x)

    (_, (want, want_stats)), (wp, wx) = run()
    calls, compiled = [], G.grouped_matmul

    def interpreted(a, w, visits):
        calls.append(a.shape)
        # what the kernels leave past the packed rows is anything at all
        past = (jnp.arange(a.shape[0]) >= visits[0][-1])[:, None]
        return jnp.where(past, jnp.nan,
                         compiled(a, w, visits, interpret=True))

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(moe.kernels, "grouped_matmul", interpreted)
    (_, (got, got_stats)), (gp, gx) = run()
    assert calls == [(bound, D)] * (2 if "gate" in p else 1) + [(bound, F)]
    close(got, want)
    close(gx, wx)
    for k in set(p) & {"router", "gate", "up", "down"}:
        close(gp[k], wp[k])
    assert got_stats == want_stats
    assert float(got_stats["rows_here"]) == T * K


# ------------------------------------------------ over an expert axis ----

@pytest.mark.parametrize("rule", RULES)
@pytest.mark.parametrize("tokens_sharded", [False, True],
                         ids=["tokens_replicated", "tokens_sharded"])
def test_expert_parallel_matches_dense(tokens_sharded, rule):
    """Eight devices, one expert each: the parts summed over the axis are
    the whole layer, whether every device brings all the tokens or an
    eighth of them, and so are the gradients taken inside the
    ``shard_map`` (the router's alike on every device)."""
    mesh = build_mesh(axes={"expert": 8})
    p, x = weights(rule=rule), tokens()
    alike = [k for k in ("router", "router_bias", "expert_bias") if k in p]
    held = [k for k in ("gate", "up", "down") if k in p]
    keys = alike + held

    def dense(p, x):
        y = reference(p, x)[0]
        return jnp.sum(y ** 2), y

    def mine(p, x):
        y, stats = layer(p, x, axis_name="expert",
                         tokens_sharded=tokens_sharded)
        return jnp.sum(y ** 2), (y, stats["rows_here"])

    def on_a_device(x, *ws):
        (_, (y, rows)), (gp, gx) = jax.value_and_grad(
            mine, argnums=(0, 1), has_aux=True)(dict(zip(keys, ws)), x)
        return (y, jax.lax.psum(rows, "expert"), gx) \
            + tuple(gp[k] for k in keys)

    spec = jax.P("expert") if tokens_sharded else jax.P()
    specs = (jax.P(),) * len(alike) + (jax.P("expert"),) * len(held)
    got, rows, gx, *gp = jax.jit(jax.shard_map(
        on_a_device, mesh=mesh, in_specs=(spec,) + specs,
        out_specs=(spec, jax.P(), spec) + specs, check_vma=False,
    ))(x, *(p[k] for k in keys))
    (_, want), (wp, wx) = jax.value_and_grad(
        dense, argnums=(0, 1), has_aux=True)(p, x)
    close(got, want)
    assert float(rows) == T * K
    close(gx, wx)
    for k, g in zip(keys, gp):
        close(g, wp[k])
