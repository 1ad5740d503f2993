"""LFM2's sparse language model (``model_type: lfm2_moe``): a decoder whose
layers differ in two ways at once.

The mixer of layer ``i`` is ``layer_types[i]``: ``conv``, a double-gated
short convolution (``[B | C | u] = x W_in``; ``y = C * conv(B * u)`` with a
causal depthwise convolution of ``conv_L_cache`` taps and no activation;
``y W_out``), or ``full_attention``, causal softmax attention over grouped
K/V heads with q and k RMS-normalised a head BEFORE a rotary over the whole
head, through the flash kernels.  Its feed-forward goes by depth: SwiGLU of
width ``intermediate_size`` in the first ``num_dense_layers`` layers, after
them a routed one (``parallel/moe.py``: a sigmoid an expert over all
``num_experts``, ``num_experts_per_tok`` taken on the scores plus a bias that
only the choice sees, weights normalised, none dropped, SwiGLU experts, no
shared expert).  A layer is ``x + mixer(rms(x))`` then ``x + ff(rms(x))``;
norms are plain RMSNorms; the head is tied to the embedding.

The equations are written out in ``tests/lfm2_reference.py``, the plain
float32 reference the tests hold this model to.  Source of the sizes:
``https://huggingface.co/LiquidAI/LFM2-8B-A1B`` (``config.json``).

A chip's share of a deployment: ``layers_here`` names the published layers
kept (each keeps the kinds its published index gives it), ``experts_held`` of
the ``num_experts`` from ``first_expert`` on live here; the router keeps its
width and the layer computes its own experts' part.  What the absent experts
would add is left out, and that partial result goes on to the next layer.

The model's scopes in a profile carry no ``ad.`` prefix (``sconv.proj``,
``sconv.mix``, ``ffn.dense``, ``attn``, ``moe.route``, ``moe.experts``; see
``models/qwen3_next.py``).
"""
import dataclasses
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu.models.llama import rope
from autodist_tpu.models.nemotron_h import RMSNorm, rms
from autodist_tpu.models.qwen3_next import STATS, _dense, causal_conv
from autodist_tpu.ops.pallas.flash_attention import flash_attention, use_flash
from autodist_tpu.ops.sparse import embedding_lookup
from autodist_tpu.parallel.moe import expert_layer

MIXERS = ("conv", "full_attention")
LFM2_8B_A1B_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = LFM2_8B_A1B_LAYER_TYPES  # as published
    num_dense_layers: int = 2                               # as published
    layers_here: Optional[Tuple[int, ...]] = None   # None: all of them
    # attention; a head is hidden_size / num_heads wide
    num_heads: int = 32
    num_kv_heads: int = 8
    rope_theta: float = 1e6
    # short convolution
    conv_L_cache: int = 3
    # feed-forward
    intermediate_size: int = 7168
    num_experts: int = 32
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1792
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    first_expert: int = 0
    experts_held: Optional[int] = None      # None: all of them
    rows_bound: Optional[int] = None        # None: the worst case
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"            # see models/gpt.py
    remat: bool = False

    @property
    def layer_kinds(self):
        """``(mixer, feed-forward)`` of every layer kept: ``"conv"`` or
        ``"full_attention"`` by the published ``layer_types``, ``"dense"``
        before the published ``num_dense_layers`` and ``"moe"`` after."""
        unknown = set(self.layer_types) - set(MIXERS)
        if unknown:
            raise ValueError(f"layer_types: {sorted(unknown)} are none of "
                             f"{MIXERS}")
        here = self.layers_here
        if here is None:
            here = range(len(self.layer_types))
        return tuple(
            (self.layer_types[i],
             "dense" if i < self.num_dense_layers else "moe") for i in here)


LFM2_TINY = Lfm2Config(
    vocab_size=128, hidden_size=64,
    layer_types=("conv", "conv", "full_attention", "conv", "conv"),
    num_dense_layers=2, layers_here=(0, 2, 3), num_heads=4, num_kv_heads=2,
    intermediate_size=96, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, experts_held=4, dtype=jnp.float32,
    attention_impl="xla")

_normal = nn.initializers.normal(0.02)


def _taps_init(key, shape, dtype=jnp.float32):
    """A depthwise convolution's usual start: uniform in ``+- 1 /
    sqrt(taps)``."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


@jax.checkpoint
def _gated_conv(b, c, u, w):
    """``C * conv(B * u)``, the mixer's elementwise stretch: float32 between
    bfloat16 tensors, a ``jax.checkpoint`` as the stretches of
    ``models/qwen3_next.py`` are (the backward pass keeps ``b``, ``c`` and
    ``u`` and runs it again)."""
    v = b.astype(jnp.float32) * u.astype(jnp.float32)
    return (c.astype(jnp.float32) * causal_conv(v, w)).astype(b.dtype)


class ShortConv(nn.Module):
    config: Lfm2Config

    @nn.compact
    def __call__(self, x):
        c = self.config
        d = c.hidden_size
        w_in = self.param("in", _normal, (d, 3 * d), jnp.float32)
        w_conv = self.param("conv", _taps_init, (c.conv_L_cache, d),
                            jnp.float32)
        w_out = self.param("out", _normal, (d, d), jnp.float32)
        with jax.named_scope("sconv.proj"):
            # one matrix, multiplied in parts (models/qwen3_next.py says why)
            b, g, u = (_dense(x, w_in[:, lo:lo + d])
                       for lo in (0, d, 2 * d))
        with jax.named_scope("sconv.mix"):
            y = _gated_conv(b, g, u, w_conv)
        with jax.named_scope("sconv.proj"):
            return _dense(y, w_out)


class Attention(nn.Module):
    config: Lfm2Config

    @nn.compact
    def __call__(self, x):
        c = self.config
        h, h_kv, d = c.num_heads, c.num_kv_heads, c.hidden_size
        hd = d // h
        w_q = self.param("q", _normal, (d, h * hd), jnp.float32)
        w_k = self.param("k", _normal, (d, h_kv * hd), jnp.float32)
        w_v = self.param("v", _normal, (d, h_kv * hd), jnp.float32)
        q_norm = self.param("q_norm", nn.initializers.ones, (hd,),
                            jnp.float32)
        k_norm = self.param("k_norm", nn.initializers.ones, (hd,),
                            jnp.float32)
        w_out = self.param("out", _normal, (h * hd, d), jnp.float32)
        b, s, _ = x.shape
        q = _dense(x, w_q).reshape(b, s, h, hd)
        k = _dense(x, w_k).reshape(b, s, h_kv, hd)
        v = _dense(x, w_v).reshape(b, s, h_kv, hd)
        pos = jnp.arange(s)
        # the norm a head first, the rotary second
        q = rope(rms(q, q_norm, c.norm_eps, c.dtype), pos, c.rope_theta)
        k = rope(rms(k, k_norm, c.norm_eps, c.dtype), pos, c.rope_theta)
        if use_flash(c.attention_impl):
            y = flash_attention(q, k, v, causal=True)       # native GQA
        else:
            bias = jnp.where(pos[:, None] >= pos[None, :], 0.0,
                             -1e9)[None, None].astype(c.dtype)
            y = jax.nn.dot_product_attention(q, k, v, bias=bias)
        return _dense(y.reshape(b, s, h * hd), w_out)


class DenseFFN(nn.Module):
    config: Lfm2Config

    @nn.compact
    def __call__(self, x):
        c = self.config
        d, f = c.hidden_size, c.intermediate_size
        w_gate = self.param("gate", _normal, (d, f), jnp.float32)
        w_up = self.param("up", _normal, (d, f), jnp.float32)
        w_down = self.param("down", _normal, (f, d), jnp.float32)
        with jax.named_scope("ffn.dense"):
            return _dense(jax.nn.silu(_dense(x, w_gate)) * _dense(x, w_up),
                          w_down)


class RoutedFFN(nn.Module):
    """The routed experts held here; returns ``(y, stats)`` with the routing
    counters of ``parallel/moe.py``."""

    config: Lfm2Config

    @nn.compact
    def __call__(self, x):
        c = self.config
        d, f = c.hidden_size, c.moe_intermediate_size
        held = c.experts_held or c.num_experts
        w_r = self.param("router", _normal, (d, c.num_experts), jnp.float32)
        # only the choice of experts reads it; it gets no gradient, and no
        # rule for moving it is published: it stays as it starts
        bias = self.param("expert_bias", nn.initializers.zeros,
                          (c.num_experts,), jnp.float32)
        w_gate = self.param("gate", _normal, (held, d, f), jnp.float32)
        w_up = self.param("up", _normal, (held, d, f), jnp.float32)
        w_down = self.param("down", _normal, (held, f, d), jnp.float32)
        b, s, _ = x.shape
        y, stats = expert_layer(
            x.reshape(b * s, d), w_r, w_gate, w_up, w_down,
            top_k=c.num_experts_per_tok, first_expert=c.first_expert,
            rows_bound=c.rows_bound, norm_topk=c.norm_topk_prob,
            score=jax.nn.sigmoid,
            select_bias=bias if c.use_expert_bias else None,
            scale=c.routed_scaling_factor, norm_eps=1e-6)
        return y.reshape(b, s, d), jnp.stack([stats[k] for k in STATS])


class Lfm2Block(nn.Module):
    """``x + mixer(rms(x))`` then ``x + ff(rms(x))``; returns ``(x, stats)``,
    ``stats`` a routed feed-forward's ``STATS`` and ``None`` from a dense
    one."""

    config: Lfm2Config
    mixer: str
    ff: str

    @nn.compact
    def __call__(self, x):
        c = self.config
        y = RMSNorm(c.norm_eps, c.dtype, name="operator_norm")(x)
        if self.mixer == "conv":
            x = x + ShortConv(c, name="sconv")(y)
        else:
            x = x + Attention(c, name="attn")(y)
        y = RMSNorm(c.norm_eps, c.dtype, name="ffn_norm")(x)
        if self.ff == "dense":
            return x + DenseFFN(c, name="ffn")(y), None
        y, stats = RoutedFFN(c, name="moe")(y)
        return x + y, stats


class Lfm2(nn.Module):
    """``(logits [B, S, V] or the last hidden states, stats)``: ``stats`` is
    ``[routed layers, 3]``, each routed layer's ``STATS``.  The head is the
    embedding ``embed``."""

    config: Lfm2Config

    @nn.compact
    def __call__(self, tokens, return_hidden=False):
        c = self.config
        kinds = c.layer_kinds
        if all(ff == "dense" for _, ff in kinds):
            raise ValueError(f"layers {c.layers_here} of {len(c.layer_types)}"
                             f" with {c.num_dense_layers} dense ones leading:"
                             " no routed layer to count")
        emb = self.param("embed", _normal, (c.vocab_size, c.hidden_size),
                         jnp.float32)
        # a plain lookup with a dense gradient (see models/qwen3_next.py)
        x = embedding_lookup(emb, tokens, sync=False).astype(c.dtype)
        block = nn.remat(Lfm2Block) if c.remat else Lfm2Block
        stats = []
        for j, (mixer, ff) in enumerate(kinds):
            x, s = block(c, mixer, ff, name=f"l_{j}")(x)
            if s is not None:
                stats.append(s)
        x = RMSNorm(c.norm_eps, c.dtype, name="norm")(x).astype(jnp.float32)
        return (x if return_hidden else x @ emb.T), jnp.stack(stats)
