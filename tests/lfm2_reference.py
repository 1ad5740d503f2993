"""Plain float32 reference of LFM2's sparse language model (``model_type:
lfm2_moe``), for training.

Straightforward ``jax.numpy``: the short convolution as shifted sums, one tap
after another, attention as a masked softmax, one head after another, the
experts as a loop over the held experts with a mask (blocks of tokens
checkpointed so that long sequences fit).  No kernels, no packing, nothing
from ``autodist_tpu``.  ``benchmark/families/lfm2.py`` holds a copy of
everything below the imports (``benchmark/tests`` checks that the two agree),
so that the yardstick imports nothing a later PR changes.

It reads the parameter tree of ``autodist_tpu/models/lfm2.py``
(``l_<j>/{operator_norm, ffn_norm, sconv | attn, ffn | moe}``, ``embed``,
``norm``) and a configuration as a plain dict ``cfg`` with the published keys
of ``config.json`` plus ``first_expert``.  ``layer_types`` holds the kinds of
the layers KEPT, in order, and the first ``num_dense_layers`` of them have the
dense feed-forward; the experts held are those whose weights the tree has.

Equations, from ``config.json`` of ``LiquidAI/LFM2-8B-A1B`` and the model
type's published description (``x`` is ``[S, hidden]``):

- Norm: ``rms(x) = x * rsqrt(mean(x^2) + eps) * w``, ``norm_eps`` 1e-5, ``w``
  initialised 1.  No bias on any matrix or on the convolution
  (``conv_bias: false``).
- A layer: ``x <- x + mixer(rms_operator(x))``; ``x <- x + ff(rms_ffn(x))``.
  After the last layer one more norm (the published model calls it
  ``embedding_norm``) and ``logits = x @ W_embed^T``: the head is TIED to the
  embedding (the config row does not carry ``tie_word_embeddings``; the
  parameter count the family states, 8.3 B, is met only with a tied head).
- ``conv``, the gated short convolution (``conv_L_cache`` taps, 3): ``[B | C
  | u] = x @ W_in``, three parts of ``hidden`` columns in this order; ``v = B
  * u``; a causal depthwise convolution over positions, no activation: ``c_t
  = w_0 v_{t-2} + w_1 v_{t-1} + w_2 v_t`` a channel (zeros before the
  sequence); ``y = C * c``; ``out = y @ W_out``.
- ``full_attention``: ``q = x @ W_q`` (``num_attention_heads`` of ``hidden /
  num_attention_heads``), ``k, v = x @ W_k, x @ W_v``
  (``num_key_value_heads``); ``q`` and ``k`` each through a norm over a
  head's entries (one weight vector for q, one for k) BEFORE the rotary;
  rotary over the whole head, pairs ``(d, d + D/2)``, ``rope_theta`` 1e6;
  causal softmax of ``q k^T / sqrt(D)``, query head ``i`` reading K/V head
  ``i // (heads / kv_heads)``; ``out = . @ W_o``.  No gate.
- Dense feed-forward (the leading ``num_dense_layers``): ``(silu(x @ W_gate)
  * (x @ W_up)) @ W_down``, width ``intermediate_size``.
- Routed feed-forward (the others): ``s = sigmoid(x @ W_r)`` over all
  ``num_experts``; the ``num_experts_per_tok`` largest of ``s + b`` (``b``
  the ``expert_bias``, ``use_expert_bias``); weights ``s`` at the chosen
  (without ``b``), divided by their sum ``+ 1e-6`` (``norm_topk_prob``),
  times ``routed_scaling_factor``; ``sum_e w_e E_e(x)`` over the chosen
  experts THAT ARE HELD, ``E`` of the dense form at width
  ``moe_intermediate_size``.  No shared expert.

Departures from the published code, none of which changes a shape: ``b`` gets
no gradient and the config gives no rule for updating it, so it stays as it
starts; the ``1e-6`` of the normalisation is assumed.
"""
import functools

import jax
import jax.numpy as jnp

TOKEN_BLOCK = 1024    # tokens per checkpointed block of the feed-forward


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def short_conv(p, x):
    s, d = x.shape
    bcu = x @ p["in"]
    b, c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    v = b * u
    taps = p["conv"].shape[0]
    conv = jnp.zeros_like(v)
    for i in range(taps):       # c_t = sum_i w_i v_{t - (taps - 1) + i}
        back = taps - 1 - i
        conv = conv + p["conv"][i] * jnp.concatenate(
            [jnp.zeros((back, d), v.dtype), v[:s - back]])
    return (c * conv) @ p["out"]


def rotary(x, theta):
    """``x`` ``[S, H, D]``: the pair ``(d, d + D/2)`` of position ``t`` is
    turned by ``t * theta^(-2d / D)``."""
    s, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(p, x, cfg):
    h, h_kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    s = x.shape[0]
    q = (x @ p["q"]).reshape(s, h, -1)
    hd = q.shape[-1]
    k = (x @ p["k"]).reshape(s, h_kv, hd)
    v = (x @ p["v"]).reshape(s, h_kv, hd)
    q = rotary(rms(q, p["q_norm"], cfg["norm_eps"]), cfg["rope_theta"])
    k = rotary(rms(k, p["k_norm"], cfg["norm_eps"]), cfg["rope_theta"])
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one_head(qkv_h):
        q_h, k_h, v_h = qkv_h
        scores = (q_h @ k_h.T) / hd ** 0.5
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1) @ v_h

    group = h // h_kv          # query head i reads K/V head i // group
    heads = jax.lax.map(one_head, tuple(
        jnp.moveaxis(t, 1, 0) for t in (
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1))))
    return jnp.moveaxis(heads, 0, 1).reshape(s, h * hd) @ p["out"]


def routed_feed_forward(p, x, cfg):
    """Returns ``(moe(x), assignments to each held expert)``."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ p["router"])
    chosen_by = scores + p["expert_bias"] \
        if cfg.get("use_expert_bias", True) else scores
    _, top_i = jax.lax.top_k(chosen_by, k)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-6)
    top_s = top_s * cfg.get("routed_scaling_factor", 1.0)
    first = cfg.get("first_expert", 0)

    def add_expert(routed, held):         # one held expert, all the tokens
        e, w_gate, w_up, w_down = held
        mine = top_i == first + e
        w_e = jnp.sum(jnp.where(mine, top_s, 0.0), axis=-1)
        return (routed + w_e[:, None] * swiglu(x, w_gate, w_up, w_down),
                jnp.sum(mine))

    return jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.arange(p["up"].shape[0]), p["gate"], p["up"], p["down"]))


def mixed(p, x, mixer, cfg):
    """A layer's first half: ``x + mixer(rms(x))``."""
    y = rms(x, p["operator_norm"]["w"], cfg["norm_eps"])
    if mixer == "conv":
        return x + short_conv(p["sconv"], y)
    return x + attention(p["attn"], y, cfg)


def feed_forward(p, x, dense, cfg):
    """A layer's second half: ``(x + ff(rms(x)), the held experts'
    assignment counts or None)``."""
    y = rms(x, p["ffn_norm"]["w"], cfg["norm_eps"])
    if dense:
        return x + swiglu(y, p["ffn"]["gate"], p["ffn"]["up"],
                          p["ffn"]["down"]), None
    # position-wise, so in blocks of tokens whose intermediates (every held
    # expert's output for every token) are computed again going backward
    rows = y.shape[0] if y.shape[0] % TOKEN_BLOCK else TOKEN_BLOCK
    y, counts = jax.lax.map(
        jax.checkpoint(lambda t: routed_feed_forward(p["moe"], t, cfg)),
        y.reshape(-1, rows, y.shape[1]))
    return x + y.reshape(x.shape), jnp.sum(counts, axis=0)


def block(p, x, mixer, dense, cfg):
    """One layer: ``(output, the held experts' counts or None)``."""
    return feed_forward(p, mixed(p, x, mixer, cfg), dense, cfg)


def hidden_states(params, tokens, cfg):
    """``tokens`` ``[S]`` -> the normed last hidden states ``[S, hidden]``
    and the held experts' assignment counts ``[routed layers,
    experts_held]``."""
    x = params["embed"][tokens]
    counts = []
    for j, mixer in enumerate(cfg["layer_types"]):
        x, c = jax.checkpoint(functools.partial(
            block, mixer=mixer, dense=j < cfg["num_dense_layers"], cfg=cfg))(
                params[f"l_{j}"], x)
        if c is not None:
            counts.append(c)
    return rms(x, params["norm"]["w"], cfg["norm_eps"]), jnp.stack(counts)


def loss(params, batch, cfg):
    """Mean next-token cross entropy over a batch ``{"tokens", "targets"}``
    of ``[B, S]``, one sequence at a time, under
    ``jax.default_matmul_precision("highest")``; the head is the embedding."""
    with jax.default_matmul_precision("highest"):
        def one(tokens, targets):
            h, _ = hidden_states(params, tokens, cfg)
            logp = jax.nn.log_softmax(h @ params["embed"].T, axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, targets[:, None], axis=-1))

        per = jax.lax.map(lambda b: one(*b),
                          (batch["tokens"], batch["targets"]))
        return jnp.mean(per)


def train_steps(params, batches, cfg, optimizer, micro_batches=1):
    """Losses of plain training steps on ``batches`` from a copy of
    ``params``: ``value_and_grad`` of ``loss`` over ``micro_batches`` equal
    parts of a batch (gradients averaged), then one optimizer update.
    Returns ``(losses, params after the last step)``.

    The optimizer's state waits on the host while a step's gradients are
    made: the device then holds weights, gradients and activations, or
    weights, gradients and moments, and never all of them."""
    import optax

    def split(b):
        return jax.tree.map(
            lambda x: x.reshape((micro_batches, -1) + x.shape[1:]), b)

    def summed(p, b):
        def body(acc, one):
            out = jax.value_and_grad(loss)(p, one, cfg)
            return jax.tree.map(jnp.add, acc, out), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        return jax.lax.scan(body, zero, split(b))[0]

    def update(p, grads, s):
        grads = jax.tree.map(lambda g: g / micro_batches, grads)
        updates, s = optimizer.update(grads, s, p)
        return optax.apply_updates(p, updates), s

    def start(p):       # a copy to donate: the caller keeps its weights
        p = jax.tree.map(jnp.copy, p)
        return p, optimizer.init(p)

    jsummed = jax.jit(summed)
    jupdate = jax.jit(update, donate_argnums=(0, 2))
    p, s = jax.jit(start)(params)
    losses = []
    for b in batches:
        s = jax.device_get(s)
        total, grads = jsummed(p, jax.tree.map(jnp.asarray, b))
        p, s = jupdate(p, grads, jax.device_put(s))
        losses.append(float(total) / micro_batches)
    return losses, p
