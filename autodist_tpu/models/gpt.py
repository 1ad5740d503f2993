"""GPT-style causal decoder LM — the long-context flagship.

Beyond the reference's model zoo (its benchmark families are BERT /
imagenet convnets / NCF / LSTM-LM): a decoder-only transformer whose
attention runs CAUSAL ring attention when the engine's ``seq`` mesh axis is
active, so context length scales with the mesh (per-device memory
O(S/num_seq_shards)) — the "long-context and distributed are first-class"
requirement.  TPU-native choices mirror ``models/bert.py``: bf16
activations / f32 params, fused QKV, pre-LayerNorm blocks, tied input/output
embedding (dense-synced, see ``ops/sparse.embedding_lookup`` contract).
"""
import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from autodist_tpu.ops.pallas.flash_attention import (flash_attention_packed,
                                                     use_flash)
from autodist_tpu.ops.sparse import embedding_lookup


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position: int = 1024
    dropout_rate: float = 0.0
    dtype: Any = jnp.bfloat16
    # "auto": Pallas flash attention on TPU, XLA elsewhere; "flash"/"xla"
    # force (flash runs in interpreter mode off-TPU — the tests' CPU path)
    attention_impl: str = "auto"
    # rematerialize each block's activations in the backward pass: peak
    # activation memory drops from O(layers * S * hidden) to O(S * hidden)
    # (+ one extra forward of FLOPs) — the long-context/deep-model lever
    remat: bool = False
    # grouped-query attention: kv heads < query heads (0 = MHA).  Shrinks
    # the decode KV cache by num_heads/num_kv_heads x; the flash kernel
    # reads shared K/V blocks straight from HBM (no repeat materialized)
    num_kv_heads: int = 0


GPT_SMALL = GPTConfig()
GPT_TINY = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                     num_heads=2, intermediate_size=128, max_position=128,
                     dtype=jnp.float32)


class CausalSelfAttention(nn.Module):
    config: GPTConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, deterministic):
        from autodist_tpu.parallel.context import current_seq_axis
        from autodist_tpu.parallel.ring_attention import ring_attention

        c = self.config
        head_dim = c.hidden_size // c.num_heads
        kv_heads = c.num_kv_heads or c.num_heads
        if c.num_heads % kv_heads:
            raise ValueError(f"num_heads {c.num_heads} not a multiple of "
                             f"num_kv_heads {kv_heads}")
        group = c.num_heads // kv_heads
        kv_dim = kv_heads * head_dim
        qkv = nn.Dense(c.hidden_size + 2 * kv_dim, dtype=c.dtype,
                       name="qkv")(x)
        q = qkv[..., :c.hidden_size]
        k = qkv[..., c.hidden_size:c.hidden_size + kv_dim]
        v = qkv[..., c.hidden_size + kv_dim:]
        B, S = x.shape[0], x.shape[1]
        q = q.reshape(B, S, c.num_heads, head_dim)
        k = k.reshape(B, S, kv_heads, head_dim)
        v = v.reshape(B, S, kv_heads, head_dim)

        def repeat_kv(t):   # GQA -> MHA for paths without native support
            return jnp.repeat(t, group, axis=2) if group > 1 else t

        seq_axis = current_seq_axis()
        if self.decode:
            # autoregressive KV cache (flax "cache" collection): x is the
            # single new token (S == 1); attend over all cached positions.
            # The cache stores KV HEADS only — the num_heads/kv_heads
            # memory saving is the point of GQA at decode time
            if seq_axis is not None:
                raise NotImplementedError("decode under sequence parallelism")
            if S != 1:
                raise ValueError(f"decode expects one token per call, got {S}")
            # flax init runs this code too: only touch the cache when it
            # already exists, so init leaves counters at zero
            cache_initialized = self.has_variable("cache", "k")
            k_cache = self.variable("cache", "k", jnp.zeros,
                                    (B, c.max_position, kv_heads, head_dim),
                                    c.dtype)
            v_cache = self.variable("cache", "v", jnp.zeros,
                                    (B, c.max_position, kv_heads, head_dim),
                                    c.dtype)
            idx = self.variable("cache", "idx",
                                lambda: jnp.zeros((), jnp.int32))
            if cache_initialized:
                t = idx.value
                k_cache.value = jax.lax.dynamic_update_slice_in_dim(
                    k_cache.value, k.astype(c.dtype), t, axis=1)
                v_cache.value = jax.lax.dynamic_update_slice_in_dim(
                    v_cache.value, v.astype(c.dtype), t, axis=1)
                idx.value = t + 1
                visible = (jnp.arange(c.max_position) <= t)
                bias = jnp.where(visible, 0.0,
                                 -1e9)[None, None, None].astype(c.dtype)
                # dot_product_attention broadcasts kv heads natively — the
                # repeated cache is never materialized
                y = jax.nn.dot_product_attention(
                    q, k_cache.value, v_cache.value, bias=bias)
            else:  # init trace: shape-correct single-token attention
                y = jax.nn.dot_product_attention(q, k, v)
        elif seq_axis is not None:
            # causal masking over GLOBAL positions while K/V blocks stream
            # around the seq ring (ring streams full-head blocks)
            y = ring_attention(q, repeat_kv(k), repeat_kv(v), seq_axis,
                               causal=True, impl=c.attention_impl)
        elif use_flash(c.attention_impl):
            # the kernels read q, k and v where the projection wrote them
            # (no slice, no transpose) and handle GQA natively
            y = flash_attention_packed(qkv, c.num_heads, kv_heads,
                                       causal=True)
        else:
            pos = jnp.arange(S)
            bias = jnp.where(pos[:, None] >= pos[None, :], 0.0,
                             -1e9)[None, None].astype(c.dtype)
            y = jax.nn.dot_product_attention(q, k, v, bias=bias)
        y = y.reshape(B, S, c.hidden_size)
        return nn.Dense(c.hidden_size, dtype=c.dtype, name="out")(y)


class GPTBlock(nn.Module):
    config: GPTConfig
    decode: bool = False

    @nn.compact
    def __call__(self, x, deterministic):
        c = self.config
        y = nn.LayerNorm(dtype=c.dtype, name="ln_1")(x)
        y = CausalSelfAttention(c, decode=self.decode, name="attn")(
            y, deterministic)
        y = nn.Dropout(c.dropout_rate)(y, deterministic=deterministic)
        x = x + y
        y = nn.LayerNorm(dtype=c.dtype, name="ln_2")(x)
        y = nn.Dense(c.intermediate_size, dtype=c.dtype, name="mlp_in")(y)
        y = nn.gelu(y)
        y = nn.Dense(c.hidden_size, dtype=c.dtype, name="mlp_out")(y)
        y = nn.Dropout(c.dropout_rate)(y, deterministic=deterministic)
        return x + y


class GPT(nn.Module):
    """Returns next-token logits (B, S, V).  ``decode=True`` switches to
    single-token autoregressive mode with per-layer KV caches (flax
    "cache" collection) — see :func:`generate`."""

    config: GPTConfig
    decode: bool = False

    @nn.compact
    def __call__(self, tokens, deterministic=True, return_hidden=False):
        from autodist_tpu.parallel.context import global_position_offset

        c = self.config
        B, S = tokens.shape
        # tied with the output head -> dense gradient (sync=False contract)
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (c.vocab_size, c.hidden_size), jnp.float32)
        wpe = self.param("wpe", nn.initializers.normal(0.02),
                         (c.max_position, c.hidden_size), jnp.float32)
        x = embedding_lookup(wte, tokens, sync=False)
        if self.decode:
            # current decode position = the attention caches' write index
            cache_initialized = self.has_variable("cache", "pos")
            t = self.variable("cache", "pos",
                              lambda: jnp.zeros((), jnp.int32))
            x = x + jax.lax.dynamic_slice_in_dim(wpe, t.value, 1)[None]
            if cache_initialized:
                t.value = t.value + 1
        else:
            pos0 = global_position_offset(S)  # seq-parallel: block start
            x = x + jax.lax.dynamic_slice_in_dim(wpe, pos0, S)[None]
        x = nn.Dropout(c.dropout_rate)(x.astype(c.dtype),
                                       deterministic=deterministic)
        block_cls = GPTBlock
        if c.remat and not self.decode:   # decode caches are tiny; skip
            block_cls = nn.remat(GPTBlock, static_argnums=(2,))
        for i in range(c.num_layers):
            x = block_cls(c, decode=self.decode, name=f"h_{i}")(
                x, deterministic)
        x = nn.LayerNorm(dtype=c.dtype, name="ln_f")(x)
        if return_hidden:
            # pre-projection activations for the streaming vocab loss
            # (ops/losses.py): the (B, S, V) logits tensor never exists
            return x.astype(jnp.float32)
        return x.astype(jnp.float32) @ wte.T


def generate(config, params, prompt, max_new_tokens, temperature=0.0,
             rng=None):
    """Autoregressive generation with per-layer KV caches (one forward per
    token, O(T) total instead of O(T^2)) — the shared jitted-scan rollout
    (``models/decoding.py``).  ``prompt``: (B, P) int32; returns
    (B, P + max_new_tokens).  ``temperature=0`` is greedy."""
    from autodist_tpu.models.decoding import generate as _generate

    return _generate(GPT(config, decode=True), config.max_position,
                     params, prompt, max_new_tokens, temperature, rng)


def gpt_loss(logits, targets, mask=None):
    """Next-token cross entropy; ``targets[t]`` is the token after position
    ``t`` (the caller shifts — under sequence parallelism each device then
    holds matching local blocks).  ``mask``: per-EXAMPLE validity from the
    session's uneven-batch padding; -100 targets are ignored per-position."""
    valid = (targets >= 0).astype(jnp.float32)
    if mask is not None:
        valid = valid * mask.reshape(mask.shape + (1,) * (valid.ndim - 1))
    safe = jnp.maximum(targets, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    return -jnp.sum(ll * valid) / jnp.maximum(jnp.sum(valid), 1.0)
