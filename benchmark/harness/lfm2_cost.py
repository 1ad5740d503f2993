"""Operations and bytes that LFM2's layers need, computed from shapes.

As ``qwen3_next_cost.py``: what the algorithm requires, whatever implements
it.  A multiply-add is two operations; a backward pass needs twice the
forward's; operations run again to save memory are not counted.
"""


def train_flops_per_token(n_dense, n_experts_held, top_k, experts_total,
                          seq_len, attn_layers, attn_width, sconv_layers,
                          sconv_channels, taps):
    """Model FLOPs a trained token: three times the forward pass of

    - a multiply-add per token for each of the ``n_dense`` matrix weights
      every token passes (projections, dense feed-forward, router, and the
      embedding once, as the tied head; the lookup and the convolutions'
      taps excluded);
    - the held experts, ``n_experts_held`` weights in all, each expert seeing
      ``top_k / experts_total`` of the tokens under a router that spreads
      them evenly;
    - the causal half of softmax attention's two S x S products over
      ``attn_width`` = heads x head size columns;
    - the short convolutions' own mix (``short_conv_flops_per_token``).
    """
    forward = (2.0 * n_dense
               + 2.0 * n_experts_held * top_k / experts_total
               + 2.0 * attn_layers * seq_len * attn_width
               + sconv_layers * short_conv_flops_per_token(sconv_channels,
                                                           taps))
    return 3.0 * forward


def short_conv_flops_per_token(channels, taps):
    """The mix itself, a channel a position: ``B * u``, ``taps``
    multiply-adds, ``C * c``."""
    return (2.0 + 2.0 * taps) * channels


def short_conv_cost(kind, tokens, channels, taps, bytes_per_el=2):
    """``(flops, bytes)`` of one layer's mix ``C * conv(B * u)`` over
    ``tokens`` positions.  ``kind`` is ``"fwd"`` or ``"bwd"`` (twice the
    operations).  Bytes: forward reads B, C and u and writes y; backward
    reads those and y's cotangent and writes a cotangent for each input.
    The taps (``taps x channels`` numbers) are not counted."""
    per_token = {"fwd": 3 + 1, "bwd": 2 * 3 + 1}[kind] * channels
    flops = {"fwd": 1.0, "bwd": 2.0}[kind] * tokens \
        * short_conv_flops_per_token(channels, taps)
    return flops, float(per_token * tokens * bytes_per_el)
