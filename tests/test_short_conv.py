"""LFM2's gated short convolution through its Pallas kernels
(``ops/pallas/short_conv.py``) in the Pallas interpreter against the XLA form
of ``models/lfm2.py`` and its ``jax.vjp``: the mix and the gradients of
``b``, ``c``, ``u`` and the taps, over several blocks of positions (a
block's halo crossing its border both ways, at the sequence's ends too),
several blocks of channels, a batch of two, three and four taps; and which
shapes the kernels take, and who takes the XLA form.

bfloat16 results against the same float32 arithmetic in another order at
one bfloat16 rounding (2**-8 of the largest entry); float32 results at 1e-5.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.models import lfm2 as L
from autodist_tpu.ops.pallas import flash_attention as F
from autodist_tpu.ops.pallas import short_conv as SC

# (shape, taps, dtype, blocks of positions and channels)
CASES = {
    # four blocks of positions of one strip each, two of channels
    "bf16-k3-one-strip-blocks": ((2, 128, 256), 3, jnp.bfloat16, (32, 128)),
    # two blocks of positions of two strips each
    "bf16-k4-two-strip-blocks": ((2, 128, 256), 4, jnp.bfloat16, (64, 128)),
    "f32-k4": ((1, 96, 256), 4, jnp.float32, (32, 128)),
}


def operands(shape, taps, dtype, seed=0):
    r = np.random.RandomState(seed)
    b, c, u, dy = (jnp.asarray(r.randn(*shape), dtype) for _ in range(4))
    return b, c, u, jnp.asarray(r.randn(taps, shape[2]), jnp.float32), dy


def mix_and_gradients(f, b, c, u, w, dy):
    y, back = jax.vjp(f, b, c, u, w)
    return (y, *back(dy))


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_are_the_xla_form_and_its_gradients(case):
    shape, taps, dtype, blocks = CASES[case]
    args = operands(shape, taps, dtype)
    kernels = SC._make(*blocks, True)
    got = jax.jit(lambda *a: mix_and_gradients(kernels, *a))(*args)
    want = jax.jit(lambda *a: mix_and_gradients(L._gated_conv_xla, *a))(
        *args)
    for name, g, t in zip(("y", "db", "dc", "du", "dw"), got, want):
        assert g.shape == t.shape and g.dtype == t.dtype, name
        rtol = 2 ** -8 if g.dtype == jnp.bfloat16 else 1e-5
        t = np.asarray(t, np.float32)
        np.testing.assert_allclose(np.asarray(g, np.float32), t, rtol=0,
                                   atol=rtol * np.abs(t).max(), err_msg=name)


def test_tiles_takes_the_cell_and_refuses_what_it_cannot_serve():
    # the benchmark's LFM2 cell: [4, 8192, 2048], three taps, bfloat16
    assert SC.tiles(4, 8192, 2048, 3) == (512, 512)
    # float32 blocks of 512 x 512 pass the VMEM budget: half the positions
    assert SC.tiles(4, 8192, 2048, 3, 4) == (256, 512)
    assert SC._vmem_bytes(512, 512, 3, 4) > SC._VMEM_BUDGET
    assert SC.tiles(1, 96, 384, 4) == (32, 128)
    for refused in [(1, 48, 64, 3),       # channels short of a lane tile
                    (1, 100, 128, 3),     # positions in no whole strip
                    (1, 128, 128, 10)]:   # more taps than a strip borrows
        assert SC.tiles(*refused) is None, refused
        with pytest.raises(ValueError):
            SC.gated_short_conv(*operands(refused[:3], refused[3],
                                          jnp.bfloat16)[:4])


def _jaxpr(f, *args):
    """``f``'s program as traced now (a fresh function: ``make_jaxpr``
    keeps the trace of one it has seen)."""
    return jax.make_jaxpr(lambda *a: f(*a))(*args)


def test_a_tpu_takes_the_kernels_where_tiles_does_and_the_xla_form_else(
        monkeypatch):
    served = operands((1, 64, 128), 3, jnp.bfloat16)[:4]
    refused = operands((1, 48, 64), 3, jnp.bfloat16)[:4]
    # the CPU: the XLA form, whatever the shapes
    assert str(_jaxpr(L._gated_conv, *served)) \
        == str(_jaxpr(L._gated_conv_xla, *served))
    monkeypatch.setattr(F, "_on_tpu", lambda: True)
    assert [e.primitive.name for e in _jaxpr(L._gated_conv, *served).eqns] \
        == ["custom_vjp_call"]
    assert "pallas_call" in str(_jaxpr(L._gated_conv, *served))
    assert str(_jaxpr(L._gated_conv, *refused)) \
        == str(_jaxpr(L._gated_conv_xla, *refused))
