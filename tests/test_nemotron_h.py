"""Nemotron-H on the CPU at a small size, against the plain float32 reference
(``tests/nemotron_h_reference.py``: the state-space recurrence over
positions, masked softmax, a loop over the held experts) and against nothing
else.

Everything here computes in float32 on both sides, so what differs is the
order of the sums: a chunk's masked matrix products and a state carried from
chunk to chunk against one rank-one update a position, packed grouped
products against masked dense ones, a streamed loss against whole logits.
That is a few float32 ulps a sum (2**-23 = 1.2e-7), grown by the depth of
the chain to some 1e-5 of the largest value: the tolerances below are 1e-4
relative to the largest entry of each tensor.  A scan state kept in bfloat16
(2**-9 = 2e-3 a rounding, forgotten again at the rate the state decays) is
wrong by more than ten times that at this size, and
``test_a_bfloat16_state_would_fail`` holds the tolerance to it; so are a
router without its selection bias and one without its scaling factor
(``test_a_dropped_bias_or_scale_would_fail``).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import nemotron_h_reference as R
from autodist_tpu.models import nemotron_h as N
from autodist_tpu.models.train_lib import nemotron_h_capture
from autodist_tpu.ops.pallas import ssd as K
from autodist_tpu.ops.ssd import ssd_chunked

C = N.NEMOTRON_H_TINY          # hidden 64, "MEM*E", 8 experts of which 4
S = 48                         # held, top-2, vocabulary 128, chunks of 16
RTOL = 1e-4

CFG = dict(
    hybrid_override_pattern="MEM*E", mamba_num_heads=4, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, conv_kernel=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, layer_norm_epsilon=1e-5,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=2.5,
    first_expert=0)

_REFERENCE = {}     # the reference's results that two cases share


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
    """``nemotron_h_capture`` with the init jitted (flax's init runs op by
    op otherwise)."""
    made = {}

    def init(key):
        made["loss_fn"], params, made["sparse"] = nemotron_h_capture(
            config, S, rng=key)
        return params

    params = jax.jit(init)(jax.random.PRNGKey(1))
    return made["loss_fn"], params, made["sparse"]


@pytest.fixture(scope="module")
def seeded():
    """Seeded weights with every norm weight and bias-like vector moved off
    its initial value (the selection bias, which starts at zero, among them:
    a test that passes only at b = 0 tests nothing) and the matrices scaled
    up so that the gates and the router are not flat."""
    loss_fn, params, sparse = capture(C)
    r = np.random.RandomState(0)
    params = jax.tree.map(
        lambda x: x + 0.1 * jnp.asarray(r.randn(*x.shape), x.dtype)
        if x.ndim == 1 else x * 5, params)
    return loss_fn, params, sparse


def scan_inputs(seed, s, h=4, p=8, g=2, n=16, b=2):
    r = np.random.RandomState(seed)
    u = jnp.asarray(r.randn(b, s, h, p), jnp.float32)
    dt = jnp.asarray(np.log1p(np.exp(r.randn(b, s, h))) * 0.3, jnp.float32)
    a = -jnp.asarray(np.arange(1, h + 1), jnp.float32)
    bb, cc = (jnp.asarray(r.randn(b, s, g, n), jnp.float32) for _ in "bc")
    d = jnp.asarray(r.randn(h), jnp.float32)
    return u, dt, a, bb, cc, d


def scan_reference(u, dt, a, b, c, d):
    """The recurrence a sequence: ``[B, S, H, P]``."""
    return jax.vmap(R.ssm_recurrent, in_axes=(0, 0, None, 0, 0, None))(
        u, dt, a, b, c, d)


# ------------------------------------------------- the state-space scan ----

@pytest.mark.parametrize("s,chunk", [
    (41, 16),       # three chunks in one block, S not a multiple of 16
    (7, 16),        # shorter than a chunk
    (70, 4),        # eighteen chunks: two blocks, the second padded
])
def test_chunked_scan_against_the_recurrence(s, chunk):
    x = scan_inputs(s, s)
    got = jax.jit(lambda *a: ssd_chunked(*a, chunk=chunk))(*x)
    close(got, jax.jit(scan_reference)(*x))


def test_chunked_scan_gradients_against_the_recurrence():
    x = scan_inputs(3, 70)
    w = jnp.asarray(np.random.RandomState(4).randn(*x[0].shape), jnp.float32)

    def grads(f):
        return jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * w),
                                argnums=range(6)))(*x)

    got = grads(lambda *a: ssd_chunked(*a, chunk=4))
    for g, t, name in zip(got, grads(scan_reference), "u dt a b c d".split()):
        try:
            close(g, t)
        except AssertionError as e:
            raise AssertionError("d" + name + str(e)) from e


def test_scan_heads_must_divide_into_the_groups():
    u, dt, a, b, c, d = scan_inputs(0, 8, h=4, g=3)
    with pytest.raises(ValueError, match="4 heads over 3 groups"):
        ssd_chunked(u, dt, a, b, c, d)


def test_a_bfloat16_state_would_fail():
    """The tolerance is tight enough to see the state's precision: the same
    recurrence with the state rounded to bfloat16 after every position is
    out by far more than ``RTOL``."""
    u, dt, a, b, c, d = (t[0] if t.ndim > 1 else t
                         for t in scan_inputs(5, 48, g=4))

    def rounded(u, dt, a, b, c, d):     # one group a head
        def step(state, t):
            u_t, dt_t, b_t, c_t = t
            state = jnp.exp(dt_t * a)[:, None, None] * state \
                + (dt_t[:, None] * u_t)[:, :, None] * b_t[:, None, :]
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
            return state, jnp.einsum("hpn,hn->hp", state, c_t) \
                + d[:, None] * u_t

        zero = jnp.zeros(u.shape[1:] + b.shape[2:])
        return jax.lax.scan(step, zero, (u, dt, b, c))[1]

    bad = worst(jax.jit(rounded)(u, dt, a, b, c, d),
                jax.jit(R.ssm_recurrent)(u, dt, a, b, c, d))
    assert bad > 10 * RTOL, bad


# ------------------------------------------------- the scan's kernels ----

def scan_and_gradients(path, seed, s, chunk, dtype=jnp.float32, **shape):
    """``y`` and the gradients of all six inputs of a weighted sum of it, by
    ``path``: the kernels in the Pallas interpreter, the scan form of
    ``ssd_chunked`` or the recurrence.  ``u``, ``B`` and ``C`` go in as
    ``dtype`` (the mixer hands both paths bfloat16 in training)."""
    u, dt, a, b, c, d = scan_inputs(seed, s, **shape)
    u, b, c = (t.astype(dtype) for t in (u, b, c))
    w = jnp.asarray(np.random.RandomState(seed + 1).randn(*u.shape),
                    jnp.float32)
    f = {"kernels": functools.partial(K.ssd_scan, chunk=chunk, dtype=dtype,
                                      interpret=True),
         "scan": functools.partial(ssd_chunked, chunk=chunk, dtype=dtype),
         "reference": scan_reference}[path]

    @jax.jit
    def run(*x):
        y, back = jax.vjp(lambda *x: f(*x).astype(jnp.float32), *x)
        return (y,) + back(w)

    return [t.astype(jnp.float32) for t in run(u, dt, a, b, c, d)]


def scans_close(got, want, rtol=RTOL):
    for g, t, name in zip(got, want, "y du ddt da db dc dd".split()):
        try:
            close(g, t, rtol)
        except AssertionError as e:
            raise AssertionError(name + str(e)) from e


SCAN_KERNEL_CASES = [
    # S not a whole number of chunks, one block; two heads of 8 a tile
    pytest.param(41, 16, {}, id="ragged"),
    # eighteen chunks: three blocks (the last padded), the state and its
    # cotangent carried from grid step to grid step
    pytest.param(70, 4, {}, id="three-blocks"),
    # shorter than a chunk, one sequence
    pytest.param(7, 16, dict(b=1), id="short"),
    # heads of 64, four to a group: two 128-lane tiles a program
    pytest.param(41, 16, dict(h=8, p=64, g=2, b=1), id="two-tiles"),
    # one head a group, a head wider than a tile
    pytest.param(41, 16, dict(h=2, p=160, g=2, b=1), id="wide-head")]


@pytest.mark.parametrize("s,chunk,shape", SCAN_KERNEL_CASES)
def test_scan_kernels_against_the_recurrence(s, chunk, shape):
    scans_close(scan_and_gradients("kernels", 3, s, chunk, **shape),
                scan_and_gradients("reference", 3, s, chunk, **shape))


def test_scan_kernels_against_the_scan_form_in_bfloat16():
    """The two paths of ``ssd_chunked`` with bfloat16 operands in their
    products (the benchmark's cell): within a few bfloat16 roundings of each
    other, since the two round different intermediates in the backward
    pass."""
    shape = dict(h=8, p=64, g=2, b=1)
    got = scan_and_gradients("kernels", 3, 70, 16, jnp.bfloat16, **shape)
    want = scan_and_gradients("scan", 3, 70, 16, jnp.bfloat16, **shape)
    scans_close(got, want, 2e-2)


@pytest.mark.parametrize("what", ["state", "skip"])
def test_scan_kernels_with_a_bfloat16_state_or_no_skip_would_fail(
        what, monkeypatch):
    """The tolerance sees the kernels' precision and every term: with the
    state rounded to bfloat16 after every chunk, or ``D u`` left out, they
    are out of it."""
    if what == "state":
        whole = K._next_state
        monkeypatch.setattr(K, "_next_state", lambda *a: whole(*a).astype(
            jnp.bfloat16).astype(jnp.float32))
    else:
        rows = K._rows
        monkeypatch.setattr(K, "_rows", lambda dt, a, d, q: rows(
            dt, a, jnp.zeros_like(d), q))
    got = scan_and_gradients("kernels", 3, 70, 4)
    want = scan_and_gradients("reference", 3, 70, 4)
    bad = max(worst(g, t) for g, t in zip(got, want))
    assert bad > 10 * RTOL, bad


@pytest.mark.parametrize("s,chunk", [
    (70, 4),    # three blocks of the kernels in two of the scan form
    (41, 16),   # one block of three chunks on both sides
])
def test_the_tpu_path_is_the_kernels(s, chunk, monkeypatch):
    """``ssd_chunked`` as it runs on a TPU, the backend and the tile rule
    answered for it here and the kernels in the Pallas interpreter: ``y``
    and the gradients from the forward and the backward kernel, against the
    recurrence."""
    from autodist_tpu.ops.pallas import flash_attention

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(K, "tiles", lambda *a, **k: True)
    monkeypatch.setattr(K, "ssd_scan", functools.partial(K.ssd_scan,
                                                         interpret=True))
    runs = []
    for name in ("_forward", "_backward"):
        kernel = getattr(K, name)
        monkeypatch.setattr(K, name, lambda *a, _k=kernel, _n=name, **k:
                            runs.append(_n) or _k(*a, **k))
    shape = dict(h=8, p=64, g=2, b=2)
    scans_close(scan_and_gradients("scan", 3, s, chunk, **shape),
                scan_and_gradients("reference", 3, s, chunk, **shape))
    assert set(runs) == {"_forward", "_backward"}


def test_scan_kernels_are_for_chunks_states_and_head_tiles_of_128():
    """Who takes which path: the kernels take chunks of 128, a state of
    whole 128-lane tiles and a group's heads in whole 128-lane tiles where a
    block fits their VMEM; everything else, and every CPU run, is the scan
    form's (``ssd_chunked`` asks the backend as the flash kernels do)."""
    assert K.tiles(128, 64, 8, 128)             # the benchmark's cell
    assert K.tiles(128, 128, 1, 128) and K.tiles(128, 64, 2, 256)
    assert not K.tiles(64, 64, 8, 128)          # a chunk of 64
    assert not K.tiles(128, 64, 8, 64)          # a state of 64
    assert not K.tiles(128, 64, 1, 128)         # (H / G) * P of 64
    assert not K.tiles(128, 96, 4, 128)         # heads across tile edges
    assert not K.tiles(128, 128, 64, 128)       # a block past the budget
    assert jax.default_backend() == "cpu"


# ------------------------------------------------------------- mixers ----

def hidden(seed):
    return jnp.asarray(np.random.RandomState(seed).randn(2, S, 64),
                       jnp.float32)


def value_and_grads(f, p, x):
    return jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(f(p, x) ** 2), argnums=(0, 1)))(p, x)


def test_mamba2_mixer_against_the_reference(seeded):
    p, x = seeded[1]["l_0"]["ssd"], hidden(7)
    got, got_g = value_and_grads(
        lambda p, x: N.Mamba2Mixer(C).apply({"params": p}, x), p, x)
    want, want_g = value_and_grads(
        lambda p, x: jax.vmap(lambda t: R.mamba2(p, t, CFG))(x), p, x)
    close(got, want)
    trees_close(got_g, want_g)


@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_attention_against_the_reference(seeded, impl):
    p, x = seeded[1]["l_3"]["attn"], hidden(8)
    c = dataclasses.replace(C, attention_impl=impl)
    got, got_g = value_and_grads(
        lambda p, x: N.Attention(c).apply({"params": p}, x), p, x)
    if "attn" not in _REFERENCE:
        _REFERENCE["attn"] = value_and_grads(
            lambda p, x: jax.vmap(lambda t: R.attention(p, t, CFG))(x), p, x)
    want, want_g = _REFERENCE["attn"]
    close(got, want)
    trees_close(got_g, want_g)


def routed(p, x):
    return N.RoutedFFN(C).apply({"params": p}, x)


def routed_reference(p, x, cfg=CFG):
    y, counts = R.routed_feed_forward(p, x.reshape(-1, x.shape[-1]), cfg)
    return y.reshape(x.shape), counts


def test_routed_feed_forward_against_the_reference(seeded):
    p, x = seeded[1]["l_1"]["moe"], hidden(9)
    got, got_g = value_and_grads(lambda p, x: routed(p, x)[0], p, x)
    want, want_g = value_and_grads(
        lambda p, x: routed_reference(p, x)[0], p, x)
    close(got, want)
    trees_close(got_g, want_g)
    assert not np.any(np.asarray(got_g[0]["router_bias"]))
    stats = np.asarray(jax.jit(lambda p, x: routed(p, x)[1])(p, x))
    counts = np.asarray(jax.jit(lambda p, x: routed_reference(p, x)[1])(p, x))
    assert stats[0] == counts.sum() and stats[2] == 0
    assert stats[1] == pytest.approx(counts.max() / counts.mean())


@pytest.mark.parametrize("what", ["bias", "scale"])
def test_a_dropped_bias_or_scale_would_fail(seeded, what):
    """The tolerance sees the router's two departures from a plain top-k of
    sigmoids: a reference that forgets the selection bias chooses other
    experts for some tokens, and one that forgets the scaling factor weighs
    all of them 2.5 times too low."""
    p, x = seeded[1]["l_1"]["moe"], hidden(9)
    got = jax.jit(lambda p, x: routed(p, x)[0])(p, x)
    if what == "bias":
        wrong = routed_reference(
            {**p, "router_bias": jnp.zeros_like(p["router_bias"])}, x)[0]
    else:
        wrong = routed_reference(p, x, {**CFG, "routed_scaling_factor": 1.0}
                                 )[0]
    assert worst(got, wrong) > 10 * RTOL


# ------------------------------------------------------- the whole model --

def test_layer_kinds_follow_the_pattern():
    assert C.layer_kinds == ("ssd", "moe", "ssd", "attn", "moe")
    kinds = N.NemotronHConfig().layer_kinds
    assert len(kinds) == 52
    assert (kinds.count("ssd"), kinds.count("moe"), kinds.count("attn")) \
        == (23, 23, 6)
    assert kinds[:9] == ("ssd", "moe", "ssd", "moe", "ssd", "attn", "moe",
                         "ssd", "moe")
    with pytest.raises(ValueError, match="none of"):
        dataclasses.replace(C, pattern="ME-").layer_kinds
    with pytest.raises(ValueError, match="no routed layer"):
        capture(dataclasses.replace(C, pattern="M*"))


def test_the_configuration_file_counts_its_parameters():
    """The benchmark's configuration, built as its family builds it: the
    count of the real parameter tree is the one the file states, and the
    issue's arithmetic."""
    from benchmark.harness import cells

    cell, config = cells.load_cell("nemotron3_nano_30b_a3b.train_fed")
    cfg = cells.load_family(config["family"]).model_config(config, cell)
    params = jax.eval_shape(
        lambda key: nemotron_h_capture(cfg, 16, rng=key)[1],
        jax.random.PRNGKey(0))
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    mamba = 2688 * 10304 + 4 * 6144 + 6144 + 3 * 64 + 4096 + 4096 * 2688
    attn = 2 * 2688 * 4096 + 2 * 2688 * 256
    routed_layer = 2688 * 128 + 128 + 2 * 2688 * 3712 + 8 * 2 * 2688 * 1856
    assert n == config["parameters"] == 666963456 \
        == 4 * mamba + attn + 4 * routed_layer + 9 * 2688 + 2688 \
        + 2 * 16384 * 2688


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
        if "router_bias" in jax.tree_util.keystr(path):
            np.testing.assert_array_equal(np.asarray(got_w), before)
