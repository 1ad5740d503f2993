"""Bucket planning edge cases + determinism.

``plan_buckets`` (kernel/synchronization/all_reduce.py) groups dense
AR-replicated vars into fused collective buckets; ``make_buckets``
(parallel/collectives.py) greedily packs (name, tensor) pairs by byte
budget.  Both orderings must be deterministic — the bucket sequence IS
the collective issue order, and every device must emit the identical
program — and both must survive the degenerate inputs a real model zoo
produces (scalars, giant single vars, mixed dtypes, empty sets).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from autodist_tpu.kernel import partitioner as part
from autodist_tpu.kernel.synchronization import all_reduce as ar
from autodist_tpu.parallel.collectives import make_buckets
from autodist_tpu.proto import synchronizers_pb2

_C = synchronizers_pb2.AllReduceSynchronizer


def _plan(name, shape, dtype=np.float32, group=0, comp=0,
          placement=part.Placement.REPLICATED,
          sync=part.SyncKind.ALL_REDUCE, sparse=False):
    return part.VarPlan(name=name, shape=shape, dtype=dtype,
                        placement=placement, sync=sync, sparse=sparse,
                        group=group, compressor=comp)


# -- plan_buckets ------------------------------------------------------------

def test_plan_buckets_empty_input():
    assert ar.plan_buckets({}, {}, {}) == []
    # plans present but none eligible (sparse / PS / sharded)
    plans = {
        "s": _plan("s", (4,), sparse=True),
        "p": _plan("p", (4,), sync=part.SyncKind.PS),
        "h": _plan("h", (4,), placement=part.Placement.SHARDED),
    }
    shapes = {n: p.shape for n, p in plans.items()}
    dtypes = {n: np.dtype(np.float32) for n in plans}
    assert ar.plan_buckets(plans, shapes, dtypes) == []


def test_plan_buckets_scalar_vars():
    """Shape-() vars count one element and bucket with their dtype/group
    peers."""
    plans = {"scalar": _plan("scalar", ()), "vec": _plan("vec", (7,))}
    shapes = {"scalar": (), "vec": (7,)}
    dtypes = {n: np.dtype(np.float32) for n in plans}
    (b,) = ar.plan_buckets(plans, shapes, dtypes)
    assert set(b.var_names) == {"scalar", "vec"}
    assert dict(zip(b.var_names, b.sizes))["scalar"] == 1
    assert b.total == 8


def test_plan_buckets_order_deterministic_across_insertion_order():
    """The sort key is the full group tuple (`kv[0]`): bucket order must
    not depend on dict insertion order, and mixed (group, dtype,
    compressor, hierarchy, dcn) combinations order stably."""
    specs = [
        ("a", 0, "float32", _C.NoneCompressor, _C.FLAT, 0),
        ("b", 0, "bfloat16", _C.NoneCompressor, _C.FLAT, 0),
        ("c", 1, "float32", _C.BF16Compressor, _C.FLAT, 0),
        ("d", 0, "float32", _C.NoneCompressor, _C.TWO_LEVEL,
         _C.Int8Compressor),
        ("e", 1, "float32", _C.NoneCompressor, _C.FLAT, 0),
    ]

    def build(order):
        plans, shapes, dtypes = {}, {}, {}
        for name, group, dt, comp, hier, dcn in order:
            plans[name] = part.VarPlan(
                name=name, shape=(4,), dtype=dt,
                placement=part.Placement.REPLICATED,
                sync=part.SyncKind.ALL_REDUCE, group=group,
                compressor=comp, hierarchy=hier, dcn_compressor=dcn)
            shapes[name] = (4,)
            dtypes[name] = np.dtype(dt)
        return ar.plan_buckets(plans, shapes, dtypes)

    fwd = build(specs)
    rev = build(list(reversed(specs)))
    assert [b.key for b in fwd] == [b.key for b in rev]
    assert [b.var_names for b in fwd] == [b.var_names for b in rev]
    # sorted by the full key tuple: group major, then dtype string, ...
    keys = [(b.var_names, b.key) for b in fwd]
    assert keys == sorted(keys, key=lambda kv: [
        next(g for n2, g, *_ in specs if n2 == kv[0][0])])
    # two-level buckets get a distinguishable key; flat keys keep the
    # pre-hierarchy format (checkpointed compressor state stays loadable)
    flat_keys = [b.key for b in fwd if b.hierarchy != _C.TWO_LEVEL]
    assert all("_h" not in k for k in flat_keys)
    (two,) = [b for b in fwd if b.hierarchy == _C.TWO_LEVEL]
    assert two.key.endswith(f"_h{_C.TWO_LEVEL}_d{_C.Int8Compressor}")


def test_plan_buckets_hierarchy_splits_buckets():
    """Same (group, dtype, codec) but different hierarchy must not fuse:
    a flat psum and a two-level decomposition cannot share one buffer."""
    plans = {
        "f": part.VarPlan(name="f", shape=(4,), dtype=np.float32,
                          placement=part.Placement.REPLICATED,
                          sync=part.SyncKind.ALL_REDUCE, hierarchy=_C.FLAT),
        "t": part.VarPlan(name="t", shape=(4,), dtype=np.float32,
                          placement=part.Placement.REPLICATED,
                          sync=part.SyncKind.ALL_REDUCE,
                          hierarchy=_C.TWO_LEVEL),
    }
    shapes = {n: (4,) for n in plans}
    dtypes = {n: np.dtype(np.float32) for n in plans}
    buckets = ar.plan_buckets(plans, shapes, dtypes)
    assert len(buckets) == 2
    assert {b.hierarchy for b in buckets} == {_C.FLAT, _C.TWO_LEVEL}


# -- make_buckets ------------------------------------------------------------

def test_make_buckets_empty():
    assert make_buckets([]) == []


def test_make_buckets_single_var_larger_than_budget():
    """One var bigger than bucket_bytes still gets (its own) bucket —
    the budget bounds fusion, it does not drop gradients."""
    big = jnp.zeros((1024,), jnp.float32)          # 4 KiB
    assert make_buckets([("big", big)], bucket_bytes=256) == [["big"]]
    small = jnp.zeros((8,), jnp.float32)
    buckets = make_buckets([("big", big), ("small", small)],
                           bucket_bytes=256)
    assert buckets == [["big"], ["small"]]


def test_make_buckets_mixed_dtype_adjacency():
    """A dtype change always cuts a bucket (fused buffers are
    single-dtype), even when bytes would still fit."""
    f32 = jnp.zeros((4,), jnp.float32)
    bf16 = jnp.zeros((4,), jnp.bfloat16)
    buckets = make_buckets(
        [("a", f32), ("b", bf16), ("c", bf16), ("d", f32)],
        bucket_bytes=1 << 20)
    assert buckets == [["a"], ["b", "c"], ["d"]]


def test_make_buckets_scalar_vars():
    scalars = [(f"s{i}", jnp.zeros((), jnp.float32)) for i in range(3)]
    assert make_buckets(scalars, bucket_bytes=8) == [["s0", "s1"], ["s2"]]


def test_make_buckets_byte_budget_boundary():
    """Exactly-at-budget fits; one byte over splits."""
    v = jnp.zeros((16,), jnp.float32)              # 64 B each
    assert make_buckets([("a", v), ("b", v)], bucket_bytes=128) \
        == [["a", "b"]]
    assert make_buckets([("a", v), ("b", v)], bucket_bytes=127) \
        == [["a"], ["b"]]


# -- determinism of the engine-visible order --------------------------------

@pytest.mark.parametrize("comp", ["NoneCompressor", "PowerSGDCompressor"])
def test_bucket_order_matches_sorted_groups(comp):
    """The transformer's collective issue order == plan_buckets order ==
    ascending (group, dtype, compressor, ...) regardless of plan dict
    ordering."""
    comp_enum = getattr(_C, comp)
    names = [f"v{i}" for i in range(6)]
    shapes = {n: (3 + i,) for i, n in enumerate(names)}
    dtypes = {n: np.dtype(np.float32) for n in names}
    plans = {n: _plan(n, shapes[n], group=i % 3, comp=comp_enum)
             for i, n in enumerate(names)}
    buckets = ar.plan_buckets(plans, shapes, dtypes)
    assert [b.key for b in buckets] == sorted(b.key for b in buckets)
    groups = [int(b.key.split("_")[0][1:]) for b in buckets]
    assert groups == sorted(groups)


# -- a bucket with nobody to reduce with --------------------------------------

@pytest.mark.parametrize("devices,comp,packed", [
    (1, "NoneCompressor", False),       # the mean of one is the gradient
    (2, "NoneCompressor", True),
    (1, "BF16Compressor", True),        # a codec rounds on one device too
])
def test_a_bucket_over_one_device_is_left_unpacked(devices, comp, packed):
    """``sync_bucketed`` over axes of one device hands a codec-free bucket's
    gradients back as they are: no concatenation, no slices (on one chip the
    compiler does not always fold them away: a router's ``[hidden, experts]``
    gradient keeps the whole buffer alive, PERF.md PR 35).  With anyone to
    reduce with, or a codec, the bucket is packed as before; the values are
    the mean either way."""
    import jax

    shapes = {"router": (8, 4), "w": (5,)}
    dtypes = {n: np.dtype(np.float32) for n in shapes}
    plans = {n: _plan(n, shapes[n], comp=getattr(_C, comp)) for n in shapes}
    buckets = ar.plan_buckets(plans, shapes, dtypes)
    states = ar.init_compressor_states(buckets)
    r = np.random.RandomState(0)
    grads = {n: jnp.asarray(r.randn(devices, *s), jnp.float32)
             for n, s in shapes.items()}

    def sync(g):
        return ar.sync_bucketed(g, buckets, states, "replica")[0]

    mapped = jax.vmap(sync, axis_name="replica")
    text = str(jax.make_jaxpr(mapped)(grads))
    assert ("concatenate" in text) is packed
    got = mapped(grads)
    for n in shapes:
        want = np.broadcast_to(np.asarray(grads[n]).mean(0), grads[n].shape)
        np.testing.assert_allclose(np.asarray(got[n]), want, rtol=1e-2
                                   if comp != "NoneCompressor" else 1e-6)
