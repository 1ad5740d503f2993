"""Collective-communication layer over named mesh axes.

TPU-native replacement for the reference's native communication backend
(SURVEY.md section 2.9): TF ``collective_ops.all_reduce/all_gather`` + gRPC
send/recv become XLA collective HLOs emitted from ``jax.lax`` primitives
inside ``shard_map``.  Group/instance keys (reference
``collective_key.py:26-70``) disappear — XLA assigns channel ids — and the
ScopedAllocator fusion (reference ``runner.py:41-45``) becomes explicit
gradient bucketing (:func:`bucketed_all_reduce`) plus XLA's own collective
combining.

All functions here must be called inside ``shard_map`` (they use collective
primitives bound to a mesh axis name).
"""
import jax
import jax.numpy as jnp
import numpy as np

from autodist_tpu.const import DEFAULT_BUCKET_BYTES


def _norm_axes(axis_name):
    """Normalize an axis argument: lists become tuples, a one-element
    tuple collapses to its bare name.  All the reduce-family helpers below
    accept a single axis name OR a tuple of names (the collective then
    spans the product of those mesh axes, like ``axis_index``/``axis_size``
    already do) — the shape the two-level hierarchical sync needs."""
    if isinstance(axis_name, (tuple, list)):
        axis_name = tuple(axis_name)
        return axis_name[0] if len(axis_name) == 1 else axis_name
    return axis_name


def all_reduce_mean(x, axis_name):
    """AllReduce-mean over the axis or axes-tuple (reference merge_op=Add,
    final_op=Div, ``compressor.py:84-96``)."""
    return jax.lax.pmean(x, _norm_axes(axis_name))


def all_reduce_sum(x, axis_name):
    return jax.lax.psum(x, _norm_axes(axis_name))


def reduce_scatter(x, axis_name, *, scatter_dimension=0, tiled=True, mean=False):
    """Reduce-scatter over the axis (or axes-tuple, major-to-minor shard
    order); the grad half of weight-update sharding."""
    axis_name = _norm_axes(axis_name)
    out = jax.lax.psum_scatter(x, axis_name, scatter_dimension=scatter_dimension, tiled=tiled)
    if mean:
        out = out / axis_size(axis_name)
    return out


def all_gather(x, axis_name, *, axis=0, tiled=True):
    """All-gather over the axis or axes-tuple (inverse of reduce_scatter's
    shard order)."""
    return jax.lax.all_gather(x, _norm_axes(axis_name), axis=axis, tiled=tiled)


def all_to_all(x, axis_name, split_axis, concat_axis):
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True)


def ppermute(x, axis_name, perm):
    """Validated collective permute: the permutation is proven
    lockstep-safe (closed cycles or a one-directional stage chain — the
    L003 predicate) before the collective is emitted."""
    from autodist_tpu.kernel.collectives import ppermute as _blessed

    return _blessed(x, axis_name, perm)


def axis_index(axis_name):
    """Flattened index over one axis name or a tuple (major-to-minor)."""
    if isinstance(axis_name, (tuple, list)):
        idx = jax.lax.axis_index(axis_name[0])
        for a in axis_name[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx
    return jax.lax.axis_index(axis_name)


def axis_size(axis_name):
    """Total size over one axis name or a tuple of names."""
    if isinstance(axis_name, (tuple, list)):
        import math

        return math.prod(jax.lax.axis_size(a) for a in axis_name)
    return jax.lax.axis_size(axis_name)


# ---------------------------------------------------------------------------
# Bucketing: flatten a group of gradients into one contiguous buffer, reduce
# once, unflatten.  Equivalent in intent to ScopedAllocator's merge of
# same-group CollectiveReduce ops (reference all_reduce_strategy.py:61-66,
# runner.py:41-45): fewer, larger collectives that saturate ICI.
# ---------------------------------------------------------------------------

def _flatten_group(tensors):
    flats = [jnp.ravel(t) for t in tensors]
    sizes = [int(np.prod(t.shape)) for t in tensors]
    return jnp.concatenate(flats) if len(flats) > 1 else flats[0], sizes


def _unflatten_group(buf, tensors, sizes):
    out, off = [], 0
    for t, sz in zip(tensors, sizes):
        out.append(jnp.reshape(jax.lax.dynamic_slice_in_dim(buf, off, sz), t.shape))
        off += sz
    return out


def fused_all_reduce(tensors, axis_name, *, mean=True, reduce_fn=None):
    """AllReduce a list of same-dtype tensors as one fused buffer."""
    if not tensors:
        return []
    buf, sizes = _flatten_group(tensors)
    if reduce_fn is not None:
        buf = reduce_fn(buf)
    else:
        buf = jax.lax.pmean(buf, axis_name) if mean else jax.lax.psum(buf, axis_name)
    return _unflatten_group(buf, tensors, sizes)


def make_buckets(named_tensors, bucket_bytes=DEFAULT_BUCKET_BYTES):
    """Greedily group (name, tensor) pairs of the same dtype into buckets of
    at most `bucket_bytes` bytes.  Returns list of lists of names."""
    buckets, cur, cur_bytes, cur_dtype = [], [], 0, None
    for name, t in named_tensors:
        nbytes = int(np.prod(t.shape)) * t.dtype.itemsize
        if cur and (cur_dtype != t.dtype or cur_bytes + nbytes > bucket_bytes):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(name)
        cur_bytes += nbytes
        cur_dtype = t.dtype
    if cur:
        buckets.append(cur)
    return buckets
