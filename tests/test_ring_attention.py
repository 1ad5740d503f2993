"""Ring attention / Ulysses sequence-parallel correctness vs full attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from autodist_tpu.parallel.mesh import build_mesh
from autodist_tpu.parallel.ring_attention import all_to_all_attention, ring_attention


def _qkv(B=2, S=64, H=4, D=8, seed=0):
    r = np.random.RandomState(seed)
    def mk():
        return jnp.asarray(r.randn(B, S, H, D), jnp.float32)

    return mk(), mk(), mk()


def _reference(q, k, v, causal):
    bias = None
    if causal:
        S = q.shape[1]
        pos = jnp.arange(S)
        bias = jnp.where(pos[:, None] >= pos[None, :], 0.0, -jnp.inf)[None, None]
    return jax.nn.dot_product_attention(q, k, v, bias=bias)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_ring_attention_matches_full(causal, impl):
    if impl == "flash" and not causal and jax.default_backend() == "cpu":
        # pre-existing (seed) failure, triaged in PR 3: ONLY the
        # non-causal flash ring lowering trips XLA:CPU's SPMD partitioner
        # ("PartitionId instruction is not supported for SPMD
        # partitioning") — causal flash and both xla paths compile fine,
        # so this is an XLA:CPU lowering gap around the axis_index use
        # whose causal-mask consumers got DCE'd, not an engine bug; needs
        # an XLA-level workaround (e.g. forcing the offset scalar varying
        # once jax.lax.pcast exists), not telemetry-adjacent.
        pytest.skip("XLA:CPU SPMD partitioner rejects PartitionId in the "
                    "non-causal flash ring lowering (pre-existing; see note)")
    mesh = build_mesh()
    q, k, v = _qkv()
    want = _reference(q, k, v, causal)

    got = jax.jit(jax.shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, "replica",
                                          causal=causal, impl=impl),
        mesh=mesh,
        in_specs=(jax.P(None, "replica"),) * 3,
        out_specs=jax.P(None, "replica"),
        check_vma=False,
    ))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_xla_ring_passes_default_vma_check():
    """The XLA ring path must be VMA-clean under shard_map's DEFAULT
    varying-manual-axes validation: the scan's (m, l, o) accumulators are
    pcast to varying before they mix with ppermute'd blocks (found by the
    Mosaic AOT harness — tools/mosaic_aot_check.py).  Pallas-kernel paths
    legitimately need check_vma=False (pallas out_shapes carry no vma)."""
    mesh = build_mesh()
    q, k, v = _qkv()
    want = _reference(q, k, v, True)
    got = jax.jit(jax.shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, "replica",
                                          causal=True, impl="xla"),
        mesh=mesh,
        in_specs=(jax.P(None, "replica"),) * 3,
        out_specs=jax.P(None, "replica"),
    ))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_gradients_match_xla_ring(causal):
    """The flash ring bwd (second ring pass: dk/dv travel with their block,
    dq accumulates locally) must match differentiating the XLA ring."""
    mesh = build_mesh()
    q, k, v = _qkv(B=1, S=32, H=2)

    def make(impl):
        f = jax.shard_map(
            lambda q_, k_, v_: ring_attention(q_, k_, v_, "replica",
                                              causal=causal, impl=impl),
            mesh=mesh, in_specs=(jax.P(None, "replica"),) * 3,
            out_specs=jax.P(None, "replica"), check_vma=False)
        return jax.jit(jax.grad(
            lambda q_, k_, v_: jnp.sum(jnp.sin(f(q_, k_, v_))),
            argnums=(0, 1, 2)))

    g_flash = make("flash")(q, k, v)
    g_xla = make("xla")(q, k, v)
    for a, b in zip(g_flash, g_xla):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_full(causal):
    mesh = build_mesh()
    q, k, v = _qkv(H=8)
    want = _reference(q, k, v, causal)

    got = jax.jit(jax.shard_map(
        lambda q_, k_, v_: all_to_all_attention(q_, k_, v_, "replica", causal=causal),
        mesh=mesh,
        in_specs=(jax.P(None, "replica"),) * 3,
        out_specs=jax.P(None, "replica"),
        check_vma=False,
    ))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_ulysses_rejects_indivisible_heads():
    mesh = build_mesh()
    q, k, v = _qkv(H=4)  # 4 heads, 8 devices
    with pytest.raises(ValueError):
        jax.jit(jax.shard_map(
            lambda q_, k_, v_: all_to_all_attention(q_, k_, v_, "replica"),
            mesh=mesh, in_specs=(jax.P(None, "replica"),) * 3,
            out_specs=jax.P(None, "replica"), check_vma=False,
        ))(q, k, v)


def test_ring_attention_long_sequence_memory_shape():
    """Each device only ever materializes S/R-sized blocks."""
    mesh = build_mesh()
    q, k, v = _qkv(S=128)
    out = jax.jit(jax.shard_map(
        lambda q_, k_, v_: ring_attention(q_, k_, v_, "replica", causal=True),
        mesh=mesh, in_specs=(jax.P(None, "replica"),) * 3,
        out_specs=jax.P(None, "replica"), check_vma=False,
    ))(q, k, v)
    assert out.shape == q.shape
    want = _reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
