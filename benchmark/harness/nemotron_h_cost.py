"""Operations and bytes that Nemotron-H's layers need, computed from shapes.

As ``qwen3_next_cost.py``: what the algorithm requires, whatever implements
it.  A multiply-add is two operations; a backward pass needs twice the
forward's; operations run again to save memory are not counted.
"""


def train_flops_per_token(n_dense, n_experts_held, top_k, experts_total,
                          seq_len, attn_layers, heads, head_dim, ssd_layers,
                          ssd_heads, ssd_head_dim, ssd_state):
    """Model FLOPs a trained token: three times the forward pass of

    - a multiply-add per token for each of the ``n_dense`` matrix weights
      every token passes (projections, convolution, router, shared expert,
      head; the looked-up embedding excluded);
    - the held experts, ``n_experts_held`` weights in all, each expert seeing
      ``top_k / experts_total`` of the tokens under a router that spreads
      them evenly;
    - the causal half of softmax attention's two S x S products;
    - the state-space recurrence (``ssd_scan_flops_per_token``).
    """
    forward = (2.0 * n_dense
               + 2.0 * n_experts_held * top_k / experts_total
               + 2.0 * attn_layers * seq_len * heads * head_dim
               + ssd_layers * ssd_scan_flops_per_token(
                   ssd_heads, ssd_head_dim, ssd_state))
    return 3.0 * forward


def ssd_scan_flops_per_token(heads, head_dim, state):
    """The recurrence itself, per position and head of ``P`` channels and a
    state of ``P x N``: the decay of the state (``P N``), the rank-one write
    ``dt u B^T`` and the read ``S C`` (``2 P N`` each)."""
    return 5.0 * head_dim * state * heads


def ssd_scan_cost(kind, tokens, heads, head_dim, groups, state,
                  bytes_per_el=2):
    """``(flops, bytes)`` of the recurrence over ``tokens`` positions of one
    layer.  ``kind`` is ``"fwd"`` or ``"bwd"`` (twice the operations).
    Bytes: forward reads u, B and C (a group's, once), the steps (float32)
    and writes y; backward reads those and y's cotangent and writes a
    cotangent for each input.  The state never needs to leave the chip's
    fast memory between positions, so it is not counted."""
    u = heads * head_dim
    bc = 2 * groups * state
    dt = heads * 4.0 / bytes_per_el
    per_token = {"fwd": u + bc + dt + u,
                 "bwd": 2 * (u + bc + dt) + u}[kind]
    flops = {"fwd": 1.0, "bwd": 2.0}[kind] * tokens \
        * ssd_scan_flops_per_token(heads, head_dim, state)
    return flops, float(per_token * tokens * bytes_per_el)


def relu2_experts_cost(kind, rows, experts_held, hidden, width,
                       bytes_per_el=2):
    """``(flops, bytes)`` of the held experts' two grouped products over
    ``rows`` assignments (``moe_rows_here``).  Forward: up and down, ``2 *
    hidden * width`` each a row; reads the rows and the ``experts_held``
    experts' weights, writes the results.  Backward: twice the operations;
    reads the rows, the weights and the results' cotangent, writes the rows'
    cotangent and a gradient for every weight."""
    weights = 2.0 * experts_held * hidden * width
    flops = {"fwd": 1.0, "bwd": 2.0}[kind] * rows * 4.0 * hidden * width
    moved = {"fwd": weights + 2.0 * rows * hidden,
             "bwd": 2.0 * weights + 3.0 * rows * hidden}[kind]
    return flops, float(moved * bytes_per_el)
