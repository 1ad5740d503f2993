"""Expert parallelism: a routed feed-forward layer that is told which experts
it holds.

The router scores ALL ``experts_total`` experts and takes the ``top_k``
largest for every token; nothing is dropped for capacity.  The layer then
computes the part of the result that its own experts give: the experts
``first_expert ... first_expert + experts_held`` whose weights it was handed.
The weights of a token's ``top_k`` are normalised over all of them, held here
or not, so the parts that the shares of a layer give add up to the whole
layer (``tests/test_moe.py``).  How the scores are made is the model's and
comes in as data (``route``: a softmax over the experts; a sigmoid an expert
with a bias that only the choice sees and a scale on the weights), and so is
an expert's body (``expert_layer``: gate and up around an activation, or up
alone).  One chip that holds a share runs it as it is; under an ``expert``
mesh axis (``axis_name``) every device holds its own share and the parts are
summed over the axis.

Static shapes throughout.  The assignments to held experts are packed,
sorted by expert, into a buffer of ``rows_bound`` rows (default: the worst
case ``T * min(top_k, experts_held)``), run through grouped matrix products
and added back onto their tokens with their weights.  ``lax.ragged_dot`` is
the grouped product's definition and what runs on a CPU.  On a TPU the
products, forward and both backward, are the Pallas kernels of
``ops/pallas/grouped_matmul.py`` wherever their tile rule takes the shapes:
the compiler's own grouped kernel skips the rows that are not there as
these do, but takes its tiles from a table, and at widths that are no
multiple of 256 (Nemotron-H's 2,688 and 1,856) it runs in 512 x 128 x 128
tiles at 5.8 % of the products' roofline, where the kernels' tiles follow
the widths (PERF.md, PR 34).  The visits of row tiles are listed once a
layer for all its products.  Nothing selects the path but the backend and
the shapes.  If more
assignments arrive than ``rows_bound`` the surplus is NOT computed and
``overflow_rows`` counts it: the caller makes the step's loss non-finite
(``models/train_lib.py``, the models' captures), so a bound set too low is
seen at once and never trains on silently.

What is still missing for expert parallelism as a strategy dimension is in
ROADMAP R1: the expert axis in the strategy space, an all-to-all exchange
that moves only the routed rows (here tokens are all-gathered), and its term
in the cost model.
"""
import functools

import jax
import jax.numpy as jnp

from autodist_tpu.ops.pallas import flash_attention
from autodist_tpu.ops.pallas import grouped_matmul as kernels
from autodist_tpu.parallel.tensor_parallel import copy_to_tp, reduce_from_tp


@functools.partial(jax.checkpoint, static_argnums=(1,))
def top_k_of(p, k, chosen_by=None):
    """``(values, indices)`` of the ``k`` largest of each row of ``p``
    ``[T, E]``, largest first, the lower index first among equals (as
    ``lax.top_k``): ``k`` passes of ``argmax`` and a masked sum, all in
    the vector unit, where a sort of every row, or a gather of the values
    and its scatter going backward, is not (PERF.md, PR 28: the gather
    alone took 3.3 ms a layer).  Differentiable in the values; the
    backward pass makes the masks again from ``p``.  With ``chosen_by``
    (``[T, E]``) the ``k`` are its largest and the values still ``p``'s."""
    rest = jax.lax.stop_gradient(p if chosen_by is None else chosen_by)
    lanes = jnp.arange(p.shape[-1], dtype=jnp.int32)
    values, picked = [], []
    for _ in range(k):
        i = jnp.argmax(rest, axis=-1).astype(jnp.int32)
        mine = lanes == i[:, None]
        picked.append(i)
        values.append(jnp.sum(jnp.where(mine, p, 0.0), axis=-1))
        rest = jnp.where(mine, -jnp.inf, rest)
    return jnp.stack(values, axis=-1), jnp.stack(picked, axis=-1)


def route(x, router_w, top_k, norm_topk=True, score=jax.nn.softmax,
          select_bias=None, scale=None, norm_eps=None):
    """``(expert indices [T, k], weights [T, k] float32)``: ``score`` of the
    router's float32 outputs over all the experts (a softmax over them, or
    ``jax.nn.sigmoid`` an expert), the ``top_k`` largest of the scores (of
    ``scores + select_bias`` ``[E]`` where one is given: the bias decides who
    is chosen and never enters a weight, nor gets a gradient), and as
    weights the chosen scores, with ``norm_topk`` divided by the sum of the
    k (``+ norm_eps``), times ``scale``."""
    logits = jnp.einsum("td,de->te", x, router_w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    p = score(logits.astype(jnp.float32))
    w, idx = top_k_of(p, top_k,
                      None if select_bias is None else p + select_bias)
    if norm_topk:
        total = jnp.sum(w, axis=-1, keepdims=True)
        w = w / (total if norm_eps is None else total + norm_eps)
    return idx, (w if scale is None else w * scale)


def pack_held(idx, first_expert, experts_held, rows_bound):
    """Pack the assignments ``idx`` ``[T, k]`` that go to the held experts,
    sorted by expert and, within one, by token.

    Returns ``(flat, group_sizes, counts)``: ``flat`` ``[rows_bound]`` the
    positions in ``idx.reshape(-1)`` of the packed assignments (rows past the
    last one hold ``T * k``), ``group_sizes`` ``[experts_held]`` the rows of
    each expert in the buffer (they add up to at most ``rows_bound``) and
    ``counts`` the assignments each held expert was sent, packed or not.

    One sort of an integer key, ``expert * (T * k) + position``, with the
    assignments that go elsewhere keyed past every held expert.
    """
    n = idx.size
    if (experts_held + 1) * n >= 2 ** 31:
        raise ValueError(f"{n} assignments over {experts_held} held experts "
                         "do not fit a 32-bit sort key")
    local = idx.reshape(-1) - first_expert
    held = (local >= 0) & (local < experts_held)
    key = jnp.where(held, local, experts_held) * n \
        + jnp.arange(n, dtype=jnp.int32)
    key = jax.lax.sort(key)[:rows_bound]
    if rows_bound > n:
        key = jnp.pad(key, (0, rows_bound - n),
                      constant_values=experts_held * n)
    flat = jnp.where(key < experts_held * n, key % n, n)
    counts = jnp.sum(
        held[:, None] & (local[:, None] == jnp.arange(experts_held)),
        axis=0, dtype=jnp.int32)
    ends = jnp.minimum(jnp.cumsum(counts), rows_bound)
    group_sizes = jnp.diff(ends, prepend=0)
    return flat, group_sizes, counts


def expert_layer(x, router_w, w_gate, w_up, w_down, *, top_k,
                 first_expert=0, rows_bound=None, norm_topk=True,
                 axis_name=None, tokens_sharded=False,
                 activation=jax.nn.silu, **scoring):
    """The held experts' part of a routed feed-forward.

    Args:
      x: ``[T, D]`` tokens.
      router_w: ``[D, experts_total]``, the whole router.
      w_gate, w_up: ``[experts_held, D, F]``; w_down: ``[experts_held, F,
        D]``: the experts ``first_expert ...`` of the layer.  An expert is
        ``(activation(x w_gate) * (x w_up)) w_down``, or with ``w_gate``
        ``None`` ``activation(x w_up) w_down``: two grouped products.
      top_k: experts a token takes; norm_topk: divide their scores by their
        sum; scoring: ``route``'s ``score``, ``select_bias``, ``scale`` and
        ``norm_eps``.
      rows_bound: rows of the packed buffer; ``None`` is the worst case.
      axis_name: inside ``shard_map`` over an expert mesh axis, the axis:
        device ``i`` holds the experts from ``i * experts_held`` and the
        parts are summed over the axis.  With ``tokens_sharded`` each device
        brings its own tokens: they are all-gathered before and the sum is
        scattered back after.

    Returns ``(y, stats)``: ``y`` ``[T, D]`` in ``x.dtype`` and ``stats``, a
    dict of float32 scalars: ``rows_here`` (assignments to held experts),
    ``load_max_over_mean`` (the fullest held expert's assignments over their
    mean) and ``overflow_rows`` (assignments past ``rows_bound``, not
    computed).
    """
    experts_held = w_up.shape[0]
    if axis_name is not None:
        # what every device of the axis holds alike enters through a copy
        # whose backward pass sums the devices' gradients (and the sum
        # leaves through a reduction whose backward pass is the identity):
        # a gradient taken inside the ``shard_map`` is then the whole
        # layer's, and alike on every device (parallel/tensor_parallel.py)
        first_expert = jax.lax.axis_index(axis_name) * experts_held
        router_w = copy_to_tp(router_w, axis_name)
        x = (jax.lax.all_gather(x, axis_name, axis=0, tiled=True)
             if tokens_sharded else copy_to_tp(x, axis_name))
    t, d = x.shape
    if rows_bound is None:
        rows_bound = t * min(top_k, experts_held)
    with jax.named_scope("moe.route"):
        idx, weights = route(x, router_w, top_k, norm_topk, **scoring)
        flat, group_sizes, counts = pack_held(idx, first_expert,
                                              experts_held, rows_bound)
        token = flat // top_k                   # t for an empty row: dropped
        w_row = jnp.take(weights.reshape(-1), flat, mode="fill",
                         fill_value=0.0)
        rows = jnp.take(x, token, axis=0, mode="fill", fill_value=0)
    with jax.named_scope("moe.experts"):
        # the kernels on a TPU (asked as the flash kernels ask, so that a
        # deviceless compile for a TPU takes the same path) where their tile
        # rule takes both products' shapes; ``ragged_dot`` everywhere else
        if flash_attention._on_tpu() and all(
                kernels.tiles(rows_bound, *w.shape[1:], experts_held,
                              x.dtype.itemsize) for w in (w_up, w_down)):
            visits = kernels.row_tiles(group_sizes, rows_bound)

            def grouped(a, w):
                return kernels.grouped_matmul(a, w, visits)
        else:
            def grouped(a, w):
                return jax.lax.ragged_dot(a, w.astype(a.dtype), group_sizes,
                                          preferred_element_type=jnp.float32)

        if w_gate is None:
            h = activation(grouped(rows, w_up))
        else:
            h = activation(grouped(rows, w_gate)) * grouped(rows, w_up)
        y = grouped(h.astype(x.dtype), w_down) * w_row[:, None]
    with jax.named_scope("moe.route"):
        # rows past the packed ones carry weight 0, but what a grouped
        # product leaves in them is not promised to be finite
        y = jnp.where((flat < idx.size)[:, None], y, 0.0)
        out = jnp.zeros((t, d), jnp.float32).at[token].add(y, mode="drop")
    if axis_name is not None:
        out = (jax.lax.psum_scatter(out, axis_name, scatter_dimension=0,
                                    tiled=True) if tokens_sharded
               else reduce_from_tp(out, axis_name))
    total = jnp.sum(counts).astype(jnp.float32)
    stats = {
        "rows_here": total,
        "load_max_over_mean": jnp.max(counts).astype(jnp.float32)
        * experts_held / jnp.maximum(total, 1.0),
        "overflow_rows": jnp.maximum(total - rows_bound, 0.0)}
    return out.astype(x.dtype), stats
