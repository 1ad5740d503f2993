"""Nemotron-H's language model (``model_type: nemotron_h``): a decoder built
from a pattern string, one pre-norm residual block a character.

``M`` is a Mamba-2 mixer (a state of ``head_dim x state`` a head in float32,
trained through the chunked scan of ``ops/ssd.py``), ``*`` causal softmax
attention over grouped K/V heads with no position signal of its own (the
Mamba layers carry position) through the flash kernels, ``E`` a routed
feed-forward (``parallel/moe.py``: a sigmoid an expert over all
``n_routed_experts``, ``num_experts_per_tok`` taken on the scores plus a bias
that only the choice sees, none dropped, experts of two matrices round
``relu(.)^2``) plus one shared expert of the same form, added ungated.  Every
layer is ``x + mixer(rms(x))`` with one mixer and nothing else; norms are
plain RMSNorms, ``x * rsqrt(mean(x^2) + eps) * w``.

The equations are written out in ``tests/nemotron_h_reference.py``, the plain
float32 reference the tests hold this model to.  Source of the sizes:
``https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``
(``config.json``).

A chip's share of an expert-parallel deployment: ``experts_held`` of the
``n_routed_experts`` from ``first_expert`` on live here; the router keeps its
width and the layer computes its own experts' part.  What the absent experts
would add is left out, and that partial result goes on to the next layer.

The model's scopes in a profile carry no ``ad.`` prefix (``ssd.proj``,
``ssd.scan``, ``attn``, ``moe.route``, ``moe.experts``, ``moe.shared``; see
``models/qwen3_next.py``).
"""
import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu.models.qwen3_next import STATS, _conv_silu, _dense
from autodist_tpu.ops.pallas.flash_attention import flash_attention, use_flash
from autodist_tpu.ops.sparse import embedding_lookup
from autodist_tpu.ops.ssd import ssd_chunked
from autodist_tpu.parallel.moe import expert_layer

KINDS = {"M": "ssd", "*": "attn", "E": "moe"}


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    # the depth the initialisers rescale by (``rescale_prenorm_residual``):
    # the published one, however many layers of the pattern are kept
    num_hidden_layers: int = 52
    # attention
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # routed feed-forward
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    first_expert: int = 0
    experts_held: Optional[int] = None      # None: all of them
    rows_bound: Optional[int] = None        # None: the worst case
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"            # see models/gpt.py
    remat: bool = False

    @property
    def layer_kinds(self):
        """``"ssd"``, ``"attn"`` or ``"moe"`` a layer, from the pattern."""
        unknown = set(self.pattern) - set(KINDS)
        if unknown:
            raise ValueError(f"pattern {self.pattern!r}: {sorted(unknown)} "
                             f"are none of {sorted(KINDS)}")
        return tuple(KINDS[ch] for ch in self.pattern)


NEMOTRON_H_TINY = NemotronHConfig(
    vocab_size=128, hidden_size=64, pattern="MEM*E", num_heads=4,
    num_kv_heads=2, head_dim=16, mamba_num_heads=4, mamba_head_dim=8,
    n_groups=2, ssm_state_size=16, chunk_size=16, n_routed_experts=8,
    num_experts_per_tok=2, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=64, experts_held=4,
    dtype=jnp.float32, attention_impl="xla")

_normal = nn.initializers.normal(0.02)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def rms(x, w, eps, dtype):
    """The plain RMSNorm over the last dim, computed in float32."""
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w).astype(dtype)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        w = self.param("w", nn.initializers.ones, (x.shape[-1],),
                       jnp.float32)
        return rms(x, w, self.eps, self.dtype)


def _out_init(config):
    """The mixers' output projections start ``sqrt(depth)`` smaller."""
    return nn.initializers.normal(
        0.02 / math.sqrt(config.num_hidden_layers))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def _dt_bias_init(config):
    """The inverse softplus of a step drawn log-uniformly between
    ``time_step_min`` and ``time_step_max``, floored."""
    lo, hi = math.log(config.time_step_min), math.log(config.time_step_max)

    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (hi - lo) + lo)
        dt = jnp.maximum(dt, config.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _gated_group_norm(y, z, w, groups, eps):
    """``rms_group(y * silu(z)) * w``: gate first, then a plain RMSNorm with
    the mean square taken over each of ``groups`` runs of channels.  Float32
    between bfloat16 tensors, a ``jax.checkpoint`` as the stretches of
    ``models/qwen3_next.py`` are."""
    x = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    x = x.reshape(x.shape[:-1] + (groups, -1))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return (x.reshape(y.shape) * w).astype(y.dtype)


class Mamba2Mixer(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        h, p, g, n = (c.mamba_num_heads, c.mamba_head_dim, c.n_groups,
                      c.ssm_state_size)
        inner, d = h * p, c.hidden_size
        conv_dim = inner + 2 * g * n
        w_in = self.param("in", _normal, (d, inner + conv_dim + h),
                          jnp.float32)
        w_conv = self.param("conv", _normal, (c.conv_kernel, conv_dim),
                            jnp.float32)
        b_conv = self.param("conv_bias", nn.initializers.zeros, (conv_dim,),
                            jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init(c), (h,), jnp.float32)
        a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (h,), jnp.float32)
        w_norm = self.param("norm", nn.initializers.ones, (inner,),
                            jnp.float32)
        w_out = self.param("out", _out_init(c), (inner, d), jnp.float32)
        b, s, _ = x.shape

        with jax.named_scope("ssd.proj"):
            # one matrix, multiplied in parts (models/qwen3_next.py says why)
            cuts = (0, inner, inner + conv_dim, inner + conv_dim + h)
            z, xbc, dt = (_dense(x, w_in[:, lo:hi])
                          for lo, hi in zip(cuts, cuts[1:]))
            xbc = _conv_silu(xbc, w_conv, b_conv)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
            u = xbc[..., :inner].reshape(b, s, h, p)
            b_in = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
            c_in = xbc[..., inner + g * n:].reshape(b, s, g, n)
        with jax.named_scope("ssd.scan"):
            y = ssd_chunked(u, dt, -jnp.exp(a_log), b_in, c_in, skip,
                            chunk=c.chunk_size, dtype=c.dtype)
        with jax.named_scope("ssd.proj"):
            y = _gated_group_norm(y.reshape(b, s, inner), z, w_norm, g,
                                  c.norm_eps)
            return _dense(y, w_out)


class Attention(nn.Module):
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        h, h_kv, hd, d = c.num_heads, c.num_kv_heads, c.head_dim, \
            c.hidden_size
        w_q = self.param("q", _normal, (d, h * hd), jnp.float32)
        w_k = self.param("k", _normal, (d, h_kv * hd), jnp.float32)
        w_v = self.param("v", _normal, (d, h_kv * hd), jnp.float32)
        w_out = self.param("out", _out_init(c), (h * hd, d), jnp.float32)
        b, s, _ = x.shape
        q = _dense(x, w_q).reshape(b, s, h, hd)
        k = _dense(x, w_k).reshape(b, s, h_kv, hd)
        v = _dense(x, w_v).reshape(b, s, h_kv, hd)
        if use_flash(c.attention_impl):
            y = flash_attention(q, k, v, causal=True)       # native GQA
        else:
            pos = jnp.arange(s)
            bias = jnp.where(pos[:, None] >= pos[None, :], 0.0,
                             -1e9)[None, None].astype(c.dtype)
            y = jax.nn.dot_product_attention(q, k, v, bias=bias)
        return _dense(y.reshape(b, s, h * hd), w_out)


class RoutedFFN(nn.Module):
    """The routed experts held here plus the shared expert; returns
    ``(y, stats)`` with the routing counters of ``parallel/moe.py``."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        c = self.config
        d, f = c.hidden_size, c.moe_intermediate_size
        fs = c.moe_shared_expert_intermediate_size
        held = c.experts_held or c.n_routed_experts
        w_r = self.param("router", _normal, (d, c.n_routed_experts),
                         jnp.float32)
        # only the choice of experts reads it; it gets no gradient, and no
        # rule for moving it is published: it stays as it starts
        bias = self.param("router_bias", nn.initializers.zeros,
                          (c.n_routed_experts,), jnp.float32)
        w_up = self.param("up", _normal, (held, d, f), jnp.float32)
        w_down = self.param("down", _normal, (held, f, d), jnp.float32)
        s_up = self.param("shared_up", _normal, (d, fs), jnp.float32)
        s_down = self.param("shared_down", _normal, (fs, d), jnp.float32)
        b, s, _ = x.shape
        flat = x.reshape(b * s, d)
        y, stats = expert_layer(
            flat, w_r, None, w_up, w_down, top_k=c.num_experts_per_tok,
            first_expert=c.first_expert, rows_bound=c.rows_bound,
            norm_topk=c.norm_topk_prob, activation=relu2,
            score=jax.nn.sigmoid, select_bias=bias,
            scale=c.routed_scaling_factor, norm_eps=1e-20)
        with jax.named_scope("moe.shared"):
            shared = _dense(relu2(_dense(flat, s_up)), s_down)
        return (y + shared).reshape(b, s, d), \
            jnp.stack([stats[k] for k in STATS])


class NemotronHBlock(nn.Module):
    """``x + mixer(rms(x))``; returns ``(x, stats)``, ``stats`` the routed
    layer's ``STATS`` and ``None`` from the other kinds."""

    config: NemotronHConfig
    kind: str

    @nn.compact
    def __call__(self, x):
        c = self.config
        y = RMSNorm(c.norm_eps, c.dtype, name="norm")(x)
        if self.kind == "moe":
            y, stats = RoutedFFN(c, name="moe")(y)
            return x + y, stats
        mixer = {"ssd": Mamba2Mixer, "attn": Attention}[self.kind]
        return x + mixer(c, name=self.kind)(y), None


class NemotronH(nn.Module):
    """``(logits [B, S, V] or the last hidden states, stats)``: ``stats`` is
    ``[routed layers, 3]``, each routed layer's ``STATS``."""

    config: NemotronHConfig

    @nn.compact
    def __call__(self, tokens, return_hidden=False):
        c = self.config
        if "moe" not in c.layer_kinds:
            raise ValueError(f"pattern {c.pattern!r} has no routed layer "
                             "(E) to count")
        emb = self.param("embed", _normal, (c.vocab_size, c.hidden_size),
                         jnp.float32)
        # a plain lookup with a dense gradient (see models/qwen3_next.py)
        x = embedding_lookup(emb, tokens, sync=False).astype(c.dtype)
        block = nn.remat(NemotronHBlock) if c.remat else NemotronHBlock
        stats = []
        for i, kind in enumerate(c.layer_kinds):
            x, s = block(c, kind, name=f"l_{i}")(x)
            if s is not None:
                stats.append(s)
        x = RMSNorm(c.norm_eps, c.dtype, name="norm")(x)
        head = self.param("lm_head", _normal, (c.hidden_size, c.vocab_size),
                          jnp.float32)
        x = x.astype(jnp.float32)
        return (x if return_hidden else x @ head), jnp.stack(stats)
