"""Qwen3-Next's language model: a decoder whose layers differ in kind.

Per period of ``full_attention_interval`` layers all but the last mix
positions with a gated DeltaNet (linear attention: a recurrent state of
``d_k x d_v`` per head, trained through the chunked rule of
``ops/gated_delta.py``), the last with gated softmax attention (an output
gate per head, RMS-normalised q and k, rotary on a part of the head,
grouped K/V heads, the flash kernels).  Every layer's feed-forward is routed
(``parallel/moe.py``: all ``num_experts`` scored, ``num_experts_per_tok``
taken, none dropped) plus one shared expert behind a sigmoid gate.  Norms are
zero-centred RMSNorms, ``x * rsqrt(mean(x^2) + eps) * (1 + w)``.

The equations are written out in ``tests/qwen3_next_reference.py``, the plain
float32 reference the tests hold this model to.  Source of the sizes:
``https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct`` (``config.json``).

A chip's share of an expert-parallel deployment: ``experts_held`` of the
``num_experts`` from ``first_expert`` on live here; the router keeps its
width and the layer computes its own experts' part (``parallel/moe.py``).
What the absent experts would add is left out, and that partial result goes
on to the next layer.

The model's scopes in a profile carry no ``ad.`` prefix (``gdn.proj``,
``gdn.rule``, ``attn``, ``moe.route``, ``moe.experts``, ``moe.shared``): the
engine's stage of an operation is the LAST ``ad.`` name of its ``op_name``,
so an ``ad.`` name in here would take the time out of ``ad.grad``.
"""
import dataclasses
import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu.models.llama import rope
from autodist_tpu.ops.gated_delta import chunk_gated_delta_rule
from autodist_tpu.ops.pallas.flash_attention import flash_attention, use_flash
from autodist_tpu.ops.sparse import embedding_lookup
from autodist_tpu.parallel.moe import expert_layer

STATS = ("rows_here", "load_max_over_mean", "overflow_rows")


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    full_attention_interval: int = 4
    # gated attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    chunk_size: int = 64
    # routed feed-forward
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    first_expert: int = 0
    experts_held: Optional[int] = None      # None: all of them
    rows_bound: Optional[int] = None        # None: the worst case
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"            # see models/gpt.py
    remat: bool = False

    @property
    def layer_types(self):
        return tuple("full_attention"
                     if (i + 1) % self.full_attention_interval == 0
                     else "linear_attention" for i in range(self.num_layers))


QWEN3_NEXT_TINY = Qwen3NextConfig(
    vocab_size=128, hidden_size=64, num_layers=4, num_heads=4,
    num_kv_heads=2, head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
    chunk_size=16, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    experts_held=4, dtype=jnp.float32, attention_impl="xla")

_normal = nn.initializers.normal(0.02)


def rms(x, w, eps, dtype):
    """The zero-centred RMSNorm over the last dim, computed in float32."""
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w)).astype(dtype)


class ZeroCentredRMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        w = self.param("w", nn.initializers.zeros, (x.shape[-1],),
                       jnp.float32)
        return rms(x, w, self.eps, self.dtype)


def _dense(x, w):
    return jnp.einsum("...d,de->...e", x, w.astype(x.dtype))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


class GatedDeltaNet(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dk, dv = c.linear_key_head_dim, c.linear_value_head_dim
        n_k, n_v = hk * dk, hv * dv
        d = c.hidden_size
        w_qkvz = self.param("qkvz", _normal, (d, 2 * n_k + 2 * n_v),
                            jnp.float32)
        w_ba = self.param("ba", _normal, (d, 2 * hv), jnp.float32)
        w_conv = self.param("conv", _normal,
                            (c.linear_conv_kernel_dim, 2 * n_k + n_v),
                            jnp.float32)
        a_log = self.param("A_log", _a_log_init, (hv,), jnp.float32)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                             jnp.float32)
        w_norm = self.param("norm", nn.initializers.ones, (dv,), jnp.float32)
        w_out = self.param("out", _normal, (n_v, d), jnp.float32)
        b, s, _ = x.shape

        with jax.named_scope("gdn.proj"):
            # one matrix, multiplied in parts: slicing the weight and not
            # the product keeps the backward pass from padding every part's
            # cotangent back to the 12,288 columns of the whole
            cuts = (0, n_k, 2 * n_k, 2 * n_k + n_v, 2 * n_k + 2 * n_v)
            q, k, v, z = (_dense(x, w_qkvz[:, lo:hi])
                          for lo, hi in zip(cuts, cuts[1:]))
            ba = _dense(x, w_ba).astype(jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
            q, k, v = (_conv_silu(t, w_conv[:, lo:hi]) for t, lo, hi in
                       zip((q, k, v), cuts, cuts[1:]))
            q = _l2norm(q.reshape(b, s, hk, dk), dk ** -0.5)
            k = _l2norm(k.reshape(b, s, hk, dk), 1.0)
        with jax.named_scope("gdn.rule"):
            o = chunk_gated_delta_rule(q, k, v.reshape(b, s, hv, dv), g,
                                       beta, chunk_size=c.chunk_size,
                                       dtype=c.dtype)
        with jax.named_scope("gdn.proj"):
            o = _gated_head_norm(o, z.reshape(b, s, hv, dv), w_norm,
                                 c.rms_norm_eps)
            return _dense(o.reshape(b, s, n_v), w_out)


# Elementwise stretches that compute in float32 between bfloat16 tensors.
# Each is a ``jax.checkpoint``: the backward pass keeps the bfloat16 inputs
# and runs the stretch again, where autodiff would keep every float32
# intermediate (at 32,768 tokens each is 0.27 to 0.54 GB, PERF.md).

def causal_conv(x, w):
    """The causal depthwise convolution over positions, in ``x``'s dtype:
    ``y_t = sum_i w_i x_{t - (K-1) + i}`` with zeros before the sequence;
    ``x`` ``[B, S, C]``, ``w`` ``[K, C]``."""
    width, s = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, i:i + s] * w[i].astype(x.dtype)
               for i in range(width))


@jax.checkpoint
def _conv_silu(x, w, bias=None):
    """``silu`` of ``causal_conv(x, w) (+ bias)``; ``bias`` ``[C]``."""
    y = causal_conv(x, w)
    if bias is not None:
        y = y + bias.astype(x.dtype)
    return jax.nn.silu(y)


@functools.partial(jax.checkpoint, static_argnums=(1,))
def _l2norm(x, scale):
    """``x / sqrt(sum(x^2) + 1e-6) * scale`` over the last dim."""
    y = x.astype(jnp.float32)
    y = y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6) * scale
    return y.astype(x.dtype)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _gated_head_norm(o, z, w, eps):
    """``rms_head(o) * silu(z)``: a plain RMSNorm over a head's values with
    weight ``w``, gated."""
    y = o.astype(jnp.float32)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps) * w
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(o.dtype)


class GatedAttention(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        h, h_kv, hd, d = c.num_heads, c.num_kv_heads, c.head_dim, \
            c.hidden_size
        w_q = self.param("q", _normal, (d, h * 2 * hd), jnp.float32)
        w_k = self.param("k", _normal, (d, h_kv * hd), jnp.float32)
        w_v = self.param("v", _normal, (d, h_kv * hd), jnp.float32)
        q_norm = self.param("q_norm", nn.initializers.zeros, (hd,),
                            jnp.float32)
        k_norm = self.param("k_norm", nn.initializers.zeros, (hd,),
                            jnp.float32)
        w_out = self.param("out", _normal, (h * hd, d), jnp.float32)
        b, s, _ = x.shape
        # per head the columns are [q | gate]; sliced at the weight (see
        # GatedDeltaNet)
        w_q = w_q.reshape(d, h, 2, hd)
        q = _dense(x, w_q[:, :, 0].reshape(d, h * hd)).reshape(b, s, h, hd)
        gate = _dense(x, w_q[:, :, 1].reshape(d, h * hd)).reshape(b, s, h,
                                                                   hd)
        k = _dense(x, w_k).reshape(b, s, h_kv, hd)
        v = _dense(x, w_v).reshape(b, s, h_kv, hd)
        q = rms(q, q_norm, c.rms_norm_eps, c.dtype)
        k = rms(k, k_norm, c.rms_norm_eps, c.dtype)
        rot = int(hd * c.partial_rotary_factor)
        pos = jnp.arange(s)

        def rotary(t):
            return jnp.concatenate(
                [rope(t[..., :rot], pos, c.rope_theta), t[..., rot:]], -1)

        q, k = rotary(q), rotary(k)
        if use_flash(c.attention_impl):
            y = flash_attention(q, k, v, causal=True)       # native GQA
        else:
            bias = jnp.where(pos[:, None] >= pos[None, :], 0.0,
                             -1e9)[None, None].astype(c.dtype)
            y = jax.nn.dot_product_attention(q, k, v, bias=bias)
        y = y * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(c.dtype)
        return _dense(y.reshape(b, s, h * hd), w_out)


class SparseMoE(nn.Module):
    """The routed experts held here plus the shared expert; returns
    ``(y, stats)`` with the routing counters of ``parallel/moe.py``."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        d, f = c.hidden_size, c.moe_intermediate_size
        fs = c.shared_expert_intermediate_size
        held = c.experts_held or c.num_experts
        w_r = self.param("router", _normal, (d, c.num_experts), jnp.float32)
        w_gate = self.param("gate", _normal, (held, d, f), jnp.float32)
        w_up = self.param("up", _normal, (held, d, f), jnp.float32)
        w_down = self.param("down", _normal, (held, f, d), jnp.float32)
        s_gate = self.param("shared_gate", _normal, (d, fs), jnp.float32)
        s_up = self.param("shared_up", _normal, (d, fs), jnp.float32)
        s_down = self.param("shared_down", _normal, (fs, d), jnp.float32)
        s_router = self.param("shared_router", _normal, (d, 1), jnp.float32)
        b, s, _ = x.shape
        flat = x.reshape(b * s, d)
        y, stats = expert_layer(
            flat, w_r, w_gate, w_up, w_down, top_k=c.num_experts_per_tok,
            first_expert=c.first_expert, rows_bound=c.rows_bound,
            norm_topk=c.norm_topk_prob)
        with jax.named_scope("moe.shared"):
            shared = _dense(jax.nn.silu(_dense(flat, s_gate))
                            * _dense(flat, s_up), s_down)
            shared = shared * jax.nn.sigmoid(
                _dense(flat, s_router).astype(jnp.float32)).astype(c.dtype)
        return (y + shared).reshape(b, s, d), \
            jnp.stack([stats[k] for k in STATS])


class Qwen3NextBlock(nn.Module):
    config: Qwen3NextConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        c = self.config
        y = ZeroCentredRMSNorm(c.rms_norm_eps, c.dtype, name="norm_1")(x)
        if self.kind == "full_attention":
            x = x + GatedAttention(c, name="attn")(y)
        else:
            x = x + GatedDeltaNet(c, name="gdn")(y)
        y = ZeroCentredRMSNorm(c.rms_norm_eps, c.dtype, name="norm_2")(x)
        y, stats = SparseMoE(c, name="moe")(y)
        return x + y, stats


class Qwen3Next(nn.Module):
    """``(logits [B, S, V] or the last hidden states, stats)``: ``stats`` is
    ``[layers, 3]``, each layer's ``STATS``."""

    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, tokens, return_hidden=False):
        c = self.config
        emb = self.param("embed", _normal, (c.vocab_size, c.hidden_size),
                         jnp.float32)
        # a plain lookup with a dense gradient: the sparse path updates only
        # the rows a step touches, which is not the AdamW of the reference
        x = embedding_lookup(emb, tokens, sync=False).astype(c.dtype)
        block = nn.remat(Qwen3NextBlock) if c.remat else Qwen3NextBlock
        stats = []
        for i, kind in enumerate(c.layer_types):
            x, s = block(c, kind, name=f"l_{i}")(x)
            stats.append(s)
        x = ZeroCentredRMSNorm(c.rms_norm_eps, c.dtype, name="norm")(x)
        head = self.param("lm_head", _normal, (c.hidden_size, c.vocab_size),
                          jnp.float32)
        x = x.astype(jnp.float32)
        return (x if return_hidden else x @ head), jnp.stack(stats)


def routing_counters(stats):
    """The step's counters from ``stats`` ``[layers, 3]``: assignments to
    held experts (mean over the layers), the fullest held expert over the
    mean (largest over the layers) and the rows past ``rows_bound`` (sum)."""
    return {"moe_rows_here": jnp.mean(stats[:, 0]),
            "moe_load_max_over_mean": jnp.max(stats[:, 1]),
            "moe_overflow_rows": jnp.sum(stats[:, 2])}
