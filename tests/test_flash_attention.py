"""Flash attention kernel: exactness vs the XLA attention path.

The kernel runs in Pallas interpreter mode on the CPU test platform
(``interpret=None`` auto-select), so these tests validate the exact tiled
online-softmax algebra the TPU executes — fwd, both backward kernels,
causal masking, key-padding masks, and the model seams (GPT / BERT
``attention_impl="flash"`` vs ``"xla"``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.ops.pallas import flash_attention as F
from autodist_tpu.ops.pallas.flash_attention import flash_attention


def ref_attn(q, k, v, causal=False, kv_mask=None):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, -1e30)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        m = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(m[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _rand(shape, dtype=jnp.float32, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def jit_grad(f, **kw):
    return jax.jit(jax.grad(f, **kw))


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla(causal):
    q, k, v = (_rand((2, 64, 2, 32), seed=i) for i in range(3))
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(out, ref_attn(q, k, v, causal=causal),
                               atol=1e-5)


def test_forward_rectangular_bf16():
    q = _rand((2, 64, 2, 32), jnp.bfloat16, 0)
    k = _rand((2, 32, 2, 32), jnp.bfloat16, 1)
    v = _rand((2, 32, 2, 32), jnp.bfloat16, 2)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(out.astype(np.float32),
                               ref_attn(q, k, v).astype(np.float32),
                               atol=5e-2)


def test_kv_mask_and_fully_masked_example():
    q, k, v = (_rand((2, 64, 2, 32), seed=i) for i in range(3))
    mask = np.ones((2, 64), bool)
    mask[0, 40:] = False       # ragged padding
    mask[1, :] = False         # a fully-padded example (uneven-batch case)
    out = flash_attention(q, k, v, kv_mask=jnp.asarray(mask),
                          block_q=32, block_k=32)
    want = ref_attn(q, k, v, kv_mask=jnp.asarray(mask))
    np.testing.assert_allclose(out[0], want[0], atol=1e-5)
    assert float(jnp.max(jnp.abs(out[1]))) == 0.0   # exact zeros, no NaN


@pytest.mark.parametrize("causal,masked", [(False, False), (True, False),
                                           (False, True)])
def test_gradients_match_xla(causal, masked):
    q, k, v = (_rand((2, 64, 2, 32), seed=i) for i in range(3))
    kv_mask = None
    if masked:
        m = np.ones((2, 64), bool)
        m[:, 40:] = False
        kv_mask = jnp.asarray(m)

    def f_flash(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, kv_mask=kv_mask, block_q=32, block_k=32)))

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(ref_attn(q, k, v, causal=causal,
                                        kv_mask=kv_mask)))

    g1 = jit_grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jit_grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4)


# what the tile program adapts to: the fold (B*H of 6 and 80 are no multiple
# of the 8 heads a program would like), one tile or several a row, the dtype
@pytest.mark.parametrize("b,h,s,dtype,causal", [
    (2, 3, 64, jnp.float32, True),       # 6 folds -> 6 heads a program
    (4, 20, 64, jnp.float32, True),      # 80 folds (gpt2_large a chip) -> 8
    (1, 7, 64, jnp.float32, False),      # a prime fold -> 7
    (1, 2, 32, jnp.float32, True),       # one tile a row
    (1, 2, 128, jnp.float32, True),      # four tiles a row
    (1, 2, 320, jnp.float32, True),      # ten: past the prefix form's cases
    (2, 2, 64, jnp.bfloat16, True),
    (2, 2, 64, jnp.bfloat16, False),
], ids=["fold6", "fold80", "fold7", "one_tile", "four_tiles", "ten_tiles",
        "bf16_causal", "bf16_full"])
def test_folds_tiles_and_dtypes_match_xla(b, h, s, dtype, causal):
    q, k, v, w = (_rand((b, s, h, 16), dtype, seed=i) for i in range(4))
    # bf16: one ulp of an output or gradient of size 2-4 is 1.6e-2
    atol_out, atol_grad = (1e-5, 1e-4) if dtype == jnp.float32 else (5e-2,) * 2

    def f(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * w.astype(jnp.float32))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)

    def ref(q, k, v):
        return ref_attn(q, k, v, causal=causal)

    np.testing.assert_allclose(flash(q, k, v).astype(np.float32),
                               ref(q, k, v).astype(np.float32), atol=atol_out)
    g1 = jit_grad(f(flash), argnums=(0, 1, 2))(q, k, v)
    g2 = jit_grad(f(ref), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        assert a.dtype == dtype
        np.testing.assert_allclose(a.astype(np.float32),
                                   b_.astype(np.float32), atol=atol_grad)


@pytest.mark.parametrize("causal,masked,dtype", [
    (True, False, jnp.float32), (False, False, jnp.float32),
    (False, True, jnp.float32), (True, False, jnp.bfloat16)],
    ids=["causal", "full", "masked", "causal_bf16"])
def test_loop_form_matches_prefix_form(monkeypatch, causal, masked, dtype):
    """A program visits its visible prefix as one slab where that fits, and
    block by block with partials merged in VMEM scratch where it does not
    (long rows; ring attention's traced offsets).  Same numbers either way,
    forward and gradients."""
    q, k, v = (_rand((2, 128, 3, 16), dtype, seed=i) for i in range(3))
    kv_mask = None
    if masked:
        m = np.ones((2, 128), bool)
        m[0, 70:] = False
        m[1, :] = False
        kv_mask = jnp.asarray(m)

    def f(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, kv_mask=kv_mask, block_q=32,
            block_k=32).astype(jnp.float32)))

    want = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
    _force_loop_form(monkeypatch)
    got = jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
    F._make_flash.cache_clear()
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == jnp.float32 \
        else dict(rtol=2e-2, atol=5e-2)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a.astype(np.float32),
                                   b.astype(np.float32), **tol)


def test_rectangular_causal_matches_xla():
    # more q rows than keys, masked from position 0 of both (the loop form:
    # a prefix's length is static per tile only on a square)
    q = _rand((2, 64, 2, 16), seed=0)
    k, v = (_rand((2, 32, 2, 16), seed=i) for i in (1, 2))

    def f(attn):
        return lambda q, k, v: jnp.sum(jnp.sin(attn(q, k, v)))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=16)

    def ref(q, k, v):
        return ref_attn(q, k, v, causal=True)

    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=1e-5)
    for a, b in zip(jit_grad(f(flash), argnums=(0, 1, 2))(q, k, v),
                    jit_grad(f(ref), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(a, b, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_no_mask_build_equals_all_true_mask_build(causal):
    """``kv_mask=None`` builds the kernels without a bias operand; an
    all-true mask builds them with one that adds 0.  Same numbers, forward
    and gradients (the biased build groups heads by example: 2 a program,
    the other all 6 folds)."""
    q, k, v = (_rand((3, 64, 2, 16), seed=i) for i in range(3))
    mask = jnp.ones((3, 64), bool)

    def f(kv_mask):
        return lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
            q, k, v, causal=causal, kv_mask=kv_mask, block_q=32, block_k=32)))

    np.testing.assert_array_equal(
        flash_attention(q, k, v, causal=causal, block_q=32, block_k=32),
        flash_attention(q, k, v, causal=causal, kv_mask=mask, block_q=32,
                        block_k=32))
    for a, b in zip(jit_grad(f(None), argnums=(0, 1, 2))(q, k, v),
                    jit_grad(f(mask), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("d,pack,bh,h,group,biased,want", [
    # the projections' layout: a program stays inside one example and takes
    # whole lane blocks (pairs of heads at D = 64)
    (64, 2, 512, 16, 1, False, 8),   # gpt2_medium.train_fed
    (64, 2, 384, 12, 1, False, 6),   # GPT-2-small in chip_smoke.py
    (64, 2, 80, 20, 1, True, 4),     # gpt2_large: 10 pairs, in 2s
    (64, 2, 28, 14, 1, False, 2),    # 7 pairs: one at a time
    (64, 2, 2, 2, 1, False, 2),
    (128, 1, 64, 32, 4, False, 4),   # GQA: the query heads of one K/V head
    (256, 1, 64, 16, 8, False, 8),   # Qwen3-Next's heads, at short rows
    # folded (ring attention's step; head sizes that fit no lane block)
    (64, 0, 512, 16, 1, False, 8),
    (80, 0, 80, 20, 1, False, 8),
    (80, 0, 80, 20, 1, True, 5),     # a per-example bias keeps a program in one
    (16, 0, 6, 3, 1, False, 6),
    (16, 0, 7, 7, 1, False, 7),
    (16, 0, 11, 11, 1, False, 1),    # a prime fold over the preference
    (64, 0, 64, 32, 4, False, 4),    # GQA under 128 lanes
])
def test_heads_per_program_follow_the_layout(d, pack, bh, h, group, biased,
                                             want):
    heads = F._Heads(d, pack)
    # rows short enough that VMEM binds nothing
    assert F._pick_heads(heads, F._together(heads, bh, h, group, biased),
                         128, 128, 2, 128, 128) == want


@pytest.mark.parametrize("pack,s,itemsize,want,raised", [
    (2, 1024, 2, 4, False),     # the benchmark's rows: 4 of the 8 heads fit
    (2, 1024, 4, 2, False),     # what the default scoped limit leaves
    (2, 8192, 2, 2, True),      # one lane block's rows: Mosaic is told a limit
    (2, 32768, 2, 2, True),
    (2, 65536, 2, 0, None),     # the XLA fallback's case
    (0, 1024, 2, 2, False),     # folded: a head's rows are padded to 128 lanes
    (0, 8192, 2, 1, True),
    (0, 65536, 2, 0, None),
])
def test_heads_per_program_shrink_to_the_vmem_budget(pack, s, itemsize, want,
                                                     raised):
    heads = F._Heads(64, pack)
    shape = (s, s, itemsize, 512, 512)
    assert F._pick_heads(heads, F._together(heads, 512, 16, 1, False),
                         *shape) == want
    if want:
        need = F._vmem_bytes(heads, want, *shape)
        assert (need > F._VMEM_BUDGET) == raised
        limit = F._tpu_params(need).vmem_limit_bytes
        assert limit == (F._VMEM_LIMIT if raised else None)


@pytest.mark.parametrize("h,group,d,align,want", [
    (16, 1, 64, 128, 2),        # GPT-2: pairs of heads in 128 lanes
    (12, 1, 64, 128, 2),
    (16, 8, 256, 128, 1),       # Qwen3-Next: a head is two lane blocks
    (32, 4, 128, 128, 1),       # Llama
    (8, 1, 16, 128, 8),
    (20, 1, 80, 128, 0),        # no lane block holds heads of 80
    (12, 3, 64, 128, 0),        # grouped K/V heads under 128 lanes
    (3, 1, 64, 128, 0),         # pairs do not divide 3 heads
    (3, 1, 16, 1, 3),           # the interpreter takes any block width
    (2, 1, 32, 1, 2),
])
def test_layout_follows_the_head_size(h, group, d, align, want):
    assert F._pack(h, group, d, align) == want


@pytest.mark.parametrize("bq,bk", [(32, 32), (64, 32), (32, 64), (1, 1)])
@pytest.mark.parametrize("q_off,k_off", [(0, 0), (128, 0), (0, 128),
                                         (64, 64)])
def test_causal_loop_bounds_are_the_visible_tiles(bq, bk, q_off, k_off):
    """The in-kernel loops run over [0, n_full) unmasked and [n_full, n_vis)
    masked k tiles (and the mirror image over q tiles): against the tile
    classes counted from the positions themselves, with ring offsets."""
    s = 128
    nq, nk = s // bq, s // bk
    qpos = q_off + np.arange(s)[:, None]
    kpos = k_off + np.arange(s)[None, :]
    vis = (qpos >= kpos).reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3)
    some, every = vis.any((2, 3)), vis.all((2, 3))
    for i in range(nq):
        n_full, n_vis = F._k_bounds(q_off + i * bq, k_off, bq, bk, nk, True)
        assert every[i, :n_full].all() and not every[i, n_full:].any()
        assert some[i, :n_vis].all() and not some[i, n_vis:].any()
    for j in range(nk):
        i_vis, i_full = F._q_bounds(k_off + j * bk, q_off, bq, bk, nq, True)
        assert every[i_full:, j].all() and not every[:i_full, j].any()
        assert some[i_vis:, j].all() and not some[:i_vis, j].any()


def _force_loop_form(monkeypatch):
    monkeypatch.setattr(F, "_SLAB_BUDGET", 0)
    F._make_flash.cache_clear()


# the projections' layout: (B, S, H*D) read and written as it lies, a head
# found in its lanes by D alone.  (d, query heads, K/V heads) with the route
# each takes, then what the kernels are built for
PROJECTION_LAYOUT = [
    # d, h, h_kv, causal, masked, dtype, loop
    (16, 8, 8, True, False, jnp.float32, False),     # 8 heads a lane block
    (16, 8, 8, False, True, jnp.float32, True),
    (16, 16, 16, True, False, jnp.bfloat16, False),  # two lane blocks
    (64, 2, 2, True, False, jnp.float32, False),     # GPT-2: a pair
    (64, 2, 2, True, False, jnp.bfloat16, False),
    (64, 4, 4, True, False, jnp.float32, True),      # two pairs, k tiles looped
    (64, 2, 2, False, True, jnp.float32, False),     # BERT: padding mask
    (64, 4, 4, False, False, jnp.bfloat16, True),
    (128, 2, 2, True, False, jnp.float32, False),    # a head a lane block
    (128, 4, 2, True, False, jnp.float32, False),    # Llama: grouped K/V
    (128, 4, 2, False, True, jnp.bfloat16, True),
    (128, 2, 1, True, False, jnp.float32, True),
    (256, 2, 2, True, False, jnp.float32, False),    # a head two lane blocks
    (256, 4, 1, True, False, jnp.bfloat16, True),    # Qwen3-Next: 4 on 1, looped
    (256, 2, 1, False, False, jnp.float32, False),
    (256, 2, 2, False, True, jnp.float32, True),
]


@pytest.mark.parametrize(
    "d,h,h_kv,causal,masked,dtype,loop", PROJECTION_LAYOUT,
    ids=[f"d{d}-{h}on{kv}-{'causal' if c else 'full'}"
         f"{'-masked' if m else ''}-{jnp.dtype(t).name}-"
         f"{'loop' if lp else 'prefix'}"
         for d, h, kv, c, m, t, lp in PROJECTION_LAYOUT])
def test_projection_layout_matches_xla(monkeypatch, d, h, h_kv, causal,
                                       masked, dtype, loop):
    """Forward and all three gradients against XLA attention, and the packed
    entry against the three-operand one, over what chooses the head-to-lane
    rule and the form of the tile program."""
    b, s = 2, 64
    assert F._pack(h, h // h_kv, d, 1) == max(1, min(128 // d, h))
    assert F._prefix(causal, s, s, 32, s)
    if loop:
        _force_loop_form(monkeypatch)
        assert not F._prefix(causal, s, s, 32, s)
    q = _rand((b, s, h, d), dtype, 0)
    k, v = (_rand((b, s, h_kv, d), dtype, i) for i in (1, 2))
    w = _rand((b, s, h, d), jnp.float32, 3)
    kv_mask = None
    if masked:
        m = np.ones((b, s), bool)
        m[0, 40:] = False
        kv_mask = jnp.asarray(m)
    kw = dict(causal=causal, kv_mask=kv_mask, block_q=32, block_k=32)

    def rep(t):
        return jnp.repeat(t, h // h_kv, axis=2)

    def f(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)

    def flash(q, k, v):
        return flash_attention(q, k, v, **kw)

    def ref(q, k, v):
        return ref_attn(q, rep(k), rep(v), causal=causal, kv_mask=kv_mask)

    def packed(q, k, v):
        qkv = jnp.concatenate([t.reshape(b, s, -1) for t in (q, k, v)], -1)
        return F.flash_attention_packed(qkv, h, h_kv, **kw)

    # bf16: one ulp of an output or gradient of size 2-4 is 1.6e-2; grouped
    # K/V heads sum several such gradients
    tol = dict(atol=2e-5, rtol=1e-5) if dtype == jnp.float32 else \
        dict(atol=5e-2 * (h // h_kv), rtol=2e-2)
    args = (q, k, v)
    out = jax.jit(flash)(*args)
    assert out.shape == q.shape and out.dtype == dtype
    np.testing.assert_allclose(out.astype(np.float32),
                               ref(*args).astype(np.float32), **tol)
    got = jit_grad(f(flash), argnums=(0, 1, 2))(*args)
    want = jit_grad(f(ref), argnums=(0, 1, 2))(*args)
    for a, b_, t in zip(got, want, args):
        assert a.shape == t.shape and a.dtype == dtype
        np.testing.assert_allclose(a.astype(np.float32),
                                   b_.astype(np.float32), **tol)
    # the packed entry runs the same kernels on the same numbers
    np.testing.assert_array_equal(jax.jit(packed)(*args), out)
    for a, b_ in zip(jit_grad(f(packed), argnums=(0, 1, 2))(*args), got):
        np.testing.assert_array_equal(a, b_)
    F._make_flash.cache_clear()


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it, but for the
    bodies of the Pallas kernels (which transpose tiles in VMEM)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("shape,kv_heads,packed", [
    ((2, 256, 16, 64), 16, False),      # gpt2_medium's heads
    ((2, 256, 16, 64), 16, True),
    ((1, 256, 16, 256), 2, False),      # Qwen3-Next's
    ((2, 256, 12, 64), 12, True),       # GPT-2-small's
], ids=["gpt2_medium", "gpt2_medium_packed", "qwen3_next", "gpt2_small"])
def test_served_shapes_are_neither_transposed_nor_sliced(shape, kv_heads,
                                                         packed):
    """Where the kernels read the projections' layout, nothing round them
    moves data: no transpose of a rank-4 operand, forward or backward, and
    under the packed entry no slice of the projection's output either."""
    b, s, h, d = shape
    assert F._pack(h, h // kv_heads, d, 128)
    q = jnp.zeros(shape, jnp.bfloat16)
    kv = jnp.zeros((b, s, kv_heads, d), jnp.bfloat16)
    if packed:
        def f(qkv):
            return jnp.sum(F.flash_attention_packed(
                qkv, h, kv_heads, causal=True).astype(jnp.float32))
        args = (jnp.zeros((b, s, 3 * h * d), jnp.bfloat16),)
    else:
        def f(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True)
                           .astype(jnp.float32))
        args = (q, kv, kv)
    jaxpr = jax.make_jaxpr(jax.grad(f, argnums=tuple(range(len(args)))))(
        *args)
    eqns = list(_eqns(jaxpr.jaxpr))
    assert sum(e.primitive.name == "pallas_call" for e in eqns) == 3
    for e in eqns:
        if e.primitive.name == "transpose":
            assert all(len(x.aval.shape) < 4 for x in e.invars), e
        assert not (packed and e.primitive.name in ("slice", "dynamic_slice"))


def test_a_head_size_that_fits_no_lane_block_is_folded():
    """D = 80 neither divides nor is a multiple of 128: the kernels read the
    folded (B*H, S, D) layout, made by a transpose, as before."""
    shape = (1, 64, 2, 80)
    q, k, v = (_rand(shape, seed=i) for i in range(3))
    assert F._pack(2, 1, 80, 1) == 0

    def f(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, causal=True,
                                               block_q=32, block_k=32)))

    jaxpr = jax.make_jaxpr(f)(q, k, v)
    assert any(e.primitive.name == "transpose"
               and len(e.invars[0].aval.shape) == 4
               for e in _eqns(jaxpr.jaxpr))
    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, block_q=32, block_k=32),
        ref_attn(q, k, v, causal=True), atol=1e-5)
    qkv = jnp.concatenate([t.reshape(1, 64, -1) for t in (q, k, v)], -1)
    np.testing.assert_array_equal(
        F.flash_attention_packed(qkv, 2, causal=True, block_q=32,
                                 block_k=32),
        flash_attention(q, k, v, causal=True, block_q=32, block_k=32))


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_gqa_matches_repeated_heads(kv_heads):
    """GQA: the kernel reads shared K/V blocks via index maps; must equal
    attention over explicitly repeated heads — fwd and all grads (dk/dv
    group-summed)."""
    h = 4
    q = _rand((2, 64, h, 16), seed=0)
    k = _rand((2, 64, kv_heads, 16), seed=1)
    v = _rand((2, 64, kv_heads, 16), seed=2)
    def rep(t):
        return jnp.repeat(t, h // kv_heads, axis=2)

    def f_gqa(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, causal=True,
                                               block_q=32, block_k=32)))

    def f_rep(q, k, v):
        return jnp.sum(jnp.sin(ref_attn(q, rep(k), rep(v), causal=True)))

    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, block_q=32, block_k=32),
        ref_attn(q, rep(k), rep(v), causal=True), atol=1e-5)
    g1 = jit_grad(f_gqa, argnums=(0, 1, 2))(q, k, v)
    g2 = jit_grad(f_rep, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=1e-4)


def test_gpt_gqa_decode_matches_full_forward():
    """MQA config: tiny KV cache (1 kv head), greedy decode must equal the
    argmax of the full forward at each position."""
    from autodist_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, num_kv_heads=1, intermediate_size=64,
                        max_position=32, dtype=jnp.float32,
                        attention_impl="xla")
    r = np.random.RandomState(0)
    prompt = r.randint(0, 128, (2, 4)).astype(np.int32)
    params = gpt.GPT(cfg).init(jax.random.PRNGKey(0),
                               jnp.asarray(prompt))["params"]
    out = np.asarray(gpt.generate(cfg, params, prompt, max_new_tokens=4))
    # oracle: recompute each next token with the full (cache-free) forward
    seq = prompt.copy()
    forward = jax.jit(lambda p, s: gpt.GPT(cfg).apply({"params": p}, s))
    for _ in range(4):
        logits = forward(params, jnp.asarray(seq))
        nxt = np.asarray(jnp.argmax(logits[:, -1], axis=-1))[:, None]
        seq = np.concatenate([seq, nxt.astype(np.int32)], axis=1)
    np.testing.assert_array_equal(out, seq)


def test_gpt_flash_matches_xla():
    from autodist_tpu.models import gpt

    cfg_x = gpt.GPT_TINY
    cfg_f = gpt.GPTConfig(**{**cfg_x.__dict__, "attention_impl": "flash"})
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 512, (2, 64)))
    params = gpt.GPT(cfg_x).init(jax.random.PRNGKey(0), tokens)["params"]

    def loss(cfg, p):
        logits = gpt.GPT(cfg).apply({"params": p}, tokens)
        return gpt.gpt_loss(logits, tokens)

    lx, gx = jax.jit(jax.value_and_grad(lambda p: loss(cfg_x, p)))(params)
    lf, gf = jax.jit(jax.value_and_grad(lambda p: loss(cfg_f, p)))(params)
    np.testing.assert_allclose(lf, lx, rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, atol=1e-4),
                 gf, gx)


def test_bert_flash_matches_xla_with_padding_mask():
    from autodist_tpu.models import bert

    cfg_x = bert.BertConfig(**{**bert.BERT_TINY.__dict__,
                               "dtype": jnp.float32})
    cfg_f = bert.BertConfig(**{**cfg_x.__dict__, "attention_impl": "flash"})
    r = np.random.RandomState(0)
    ids = jnp.asarray(r.randint(0, 1024, (2, 64)))
    mask = np.ones((2, 64), bool)
    mask[1, 48:] = False
    mask = jnp.asarray(mask)
    model_x, model_f = bert.Bert(cfg_x), bert.Bert(cfg_f)
    params = model_x.init(jax.random.PRNGKey(0), ids)["params"]

    def pooled(model, p):
        x, _ = model.apply({"params": p}, ids, attention_mask=mask)
        # compare only valid positions (padded-query rows differ by design)
        return jnp.sum(jnp.sin(x) * mask[:, :, None])

    vx, gx = jax.jit(jax.value_and_grad(lambda p: pooled(model_x, p)))(params)
    vf, gf = jax.jit(jax.value_and_grad(lambda p: pooled(model_f, p)))(params)
    np.testing.assert_allclose(vf, vx, rtol=1e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-3,
                                                         atol=1e-3),
                 gf, gx)
