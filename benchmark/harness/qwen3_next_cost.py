"""Operations and bytes that Qwen3-Next's layers need, computed from shapes.

As ``flops.py``: what the algorithm requires, whatever implements it.  A
multiply-add is two operations; a backward pass needs twice the forward's;
operations run again to save memory are not counted.
"""


def train_flops_per_token(n_dense, n_experts_held, top_k, experts_total,
                          seq_len, attn_layers, heads, head_dim, gdn_layers,
                          value_heads, key_dim, value_dim):
    """Model FLOPs a trained token: three times the forward pass of

    - a multiply-add per token for each of the ``n_dense`` matrix weights
      every token passes (projections, router, shared expert, head; the
      looked-up embedding excluded);
    - the held experts, ``n_experts_held`` weights in all, each expert seeing
      ``top_k / experts_total`` of the tokens under a router that spreads
      them evenly;
    - the causal half of softmax attention's two S x S products;
    - the delta rule (``gdn_rule_cost``).
    """
    forward = (2.0 * n_dense
               + 2.0 * n_experts_held * top_k / experts_total
               + 2.0 * attn_layers * seq_len * heads * head_dim
               + gdn_layers * gdn_rule_flops_per_token(value_heads, key_dim,
                                                       value_dim))
    return 3.0 * forward


def gdn_rule_flops_per_token(value_heads, key_dim, value_dim):
    """The recurrence itself, per position and value head: the decay of the
    state (``d_k * d_v``) and three products with it, ``S^T k``, ``k u^T``
    and ``S^T q`` (``2 * d_k * d_v`` each)."""
    return 7.0 * key_dim * value_dim * value_heads


def gdn_rule_cost(kind, tokens, key_heads, value_heads, key_dim, value_dim,
                  bytes_per_el=2):
    """``(flops, bytes)`` of the delta rule over ``tokens`` positions of one
    layer.  ``kind`` is ``"fwd"`` or ``"bwd"`` (twice the operations).
    Bytes: forward reads q, k (key heads), v, the two gates (float32) and
    writes o; backward reads those, o's cotangent, and writes a cotangent
    for each input.  The state never needs to leave the chip's fast memory
    between positions, so it is not counted."""
    qk = 2 * key_heads * key_dim
    v = value_heads * value_dim
    gates = 2 * value_heads * 4.0 / bytes_per_el
    per_token = {"fwd": qk + v + gates + v,
                 "bwd": 2 * (qk + v + gates) + v}[kind]
    flops = {"fwd": 1.0, "bwd": 2.0}[kind] * tokens \
        * gdn_rule_flops_per_token(value_heads, key_dim, value_dim)
    return flops, float(per_token * tokens * bytes_per_el)


def moe_experts_cost(kind, rows, experts_held, hidden, width,
                     bytes_per_el=2):
    """``(flops, bytes)`` of the held experts' three grouped products over
    ``rows`` assignments (``moe_rows_here``).  Forward: gate, up and down,
    ``2 * hidden * width`` each a row; reads the rows and the
    ``experts_held`` experts' weights, writes the results.  Backward: twice
    the operations; reads the rows, the weights and the results' cotangent,
    writes the rows' cotangent and a gradient for every weight."""
    weights = 3.0 * experts_held * hidden * width
    flops = {"fwd": 1.0, "bwd": 2.0}[kind] * rows * 6.0 * hidden * width
    moved = {"fwd": weights + 2.0 * rows * hidden,
             "bwd": 2.0 * weights + 3.0 * rows * hidden}[kind]
    return flops, float(moved * bytes_per_el)


def gqa_attention_call_cost(kind, batch, heads, kv_heads, seq_len, head_dim,
                            bytes_per_el=2):
    """``(flops, bytes)`` of one causal attention call with grouped K/V
    heads: ``flops.flash_attention_call_cost`` with K and V (and their
    cotangents) counted at ``kv_heads``."""
    matmuls = {"fwd": 2, "bwd": 5}[kind]
    flops = matmuls * float(seq_len) * seq_len * head_dim * batch * heads
    q_like, kv_like = {"fwd": (2, 2), "bwd": (4, 4)}[kind]
    moved = batch * seq_len * head_dim * bytes_per_el \
        * (q_like * heads + kv_like * kv_heads)
    return flops, float(moved)
