"""Operations and bytes that the work requires, computed from shapes.

Model FLOPs count what the forward and backward passes need (backward = 2x
forward); operations recomputed to save memory are not counted.  Copied from
``bench.py:_build_gpt`` and ``bench.py:MODELS`` (sound arithmetic there).
"""

# Forward pass of ResNet-50 v1.5 at 224x224, as ``bench.py`` has it: 4.089e9
# per image, a multiply-add counted once (He et al. 2016, Table 1 gives 3.8e9
# for v1; stride 2 in the 3x3 convolution costs the rest).  The usual
# convention for this model, kept so that numbers compare with published MFUs.
RESNET50_FWD_FLOPS_PER_IMAGE = 4.089e9


def resnet50_train_flops_per_image():
    return 3.0 * RESNET50_FWD_FLOPS_PER_IMAGE


def gpt_param_count(vocab, hidden, layers, ff, positions):
    """Parameters of a GPT-2 (tied head, biases and LayerNorms counted):
    ``(all, without the position table)``."""
    block = (hidden * 3 * hidden + 3 * hidden      # qkv
             + hidden * hidden + hidden            # attention out
             + hidden * ff + ff                    # mlp in
             + ff * hidden + hidden                # mlp out
             + 4 * hidden)                         # two LayerNorms
    n_matmul = vocab * hidden + layers * block + 2 * hidden
    return n_matmul + positions * hidden, n_matmul


def gpt_train_flops_per_token(n_matmul_params, layers, seq_len, hidden):
    """``3 * (2 * N + 2 * L * S * hidden)``: a multiply-add per parameter
    per token (the looked-up position table excluded), plus the causal half
    of attention's two S x S matmuls; times three for training."""
    return 3.0 * (2.0 * n_matmul_params + 2.0 * layers * seq_len * hidden)


def flash_attention_call_cost(kind, batch, heads, seq_len, head_dim,
                              bytes_per_el=2):
    """``(flops, bytes)`` that one causal attention call needs.

    ``kind`` is ``"fwd"`` (QK^T and PV: 2 matmuls) or ``"bwd"`` (S again,
    dV, dP, dQ, dK: 5 matmuls).  A causal S x S x D matmul is half of
    ``2 * S * S * D`` FLOPs.  Bytes: forward reads q, k, v and writes o;
    backward reads q, k, v, o, do and writes dq, dk, dv (the f32 row
    statistics are 1/64 of one of those and left out).
    """
    matmuls, tensors = {"fwd": (2, 4), "bwd": (5, 8)}[kind]
    per_head = float(seq_len) * seq_len * head_dim
    flops = matmuls * per_head * batch * heads
    moved = tensors * batch * heads * seq_len * head_dim * bytes_per_el
    return flops, float(moved)


def roofline_least_seconds(flops, moved_bytes, peaks):
    """The least time the chip could take, and which peak bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = moved_bytes / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
