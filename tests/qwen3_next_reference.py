"""Plain float32 reference of Qwen3-Next's language model, for training.

Straightforward ``jax.numpy``: the recurrences as ``lax.scan`` over positions
(checkpointed in blocks of positions so that long sequences fit), attention
as a masked softmax, one head after another, the experts as a loop over the
held experts with a mask.  No kernels, no chunks, no packing, nothing from
``autodist_tpu``.  ``benchmark/families/qwen3_next.py`` holds a copy of
everything below the imports (``benchmark/tests`` checks that the two agree),
so that the yardstick imports nothing a later PR changes.

It reads the parameter tree of ``autodist_tpu/models/qwen3_next.py``
(``l_<i>/{norm_1, gdn | attn, norm_2, moe}``, ``embed``, ``norm``,
``lm_head``) and a configuration as a plain dict ``cfg`` with the published
keys of ``config.json`` plus ``experts_held`` and ``first_expert``.

Equations, from the model's ``config.json`` and the family's description
(``x`` is ``[S, hidden]``):

- Norm: ``rms(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)``.
- Block: ``h = x + mixer(rms_1(x))``; ``y = h + moe(rms_2(h))``; mixer ``l``
  is full attention where ``(l + 1) % full_attention_interval == 0``, gated
  DeltaNet otherwise; a last norm, then ``logits = h @ W_head`` (untied).
- Gated DeltaNet: ``[q, k, v, z] = x @ W_qkvz``, ``[b, a] = x @ W_ba``;
  ``[q, k, v] = silu(causal depthwise conv1d([q, k, v], width 4, no bias))``;
  per key head ``q = l2norm(q) / sqrt(d_k)``, ``k = l2norm(k)``; each key
  head serves ``H_v / H_k`` consecutive value heads; per value head
  ``beta_t = sigmoid(b_t)``, ``g_t = -exp(A_log) * softplus(a_t + dt_bias)``;
  from ``S_0 = 0``: ``S' = exp(g_t) S_{t-1}``; ``u_t = beta_t (v_t - S'^T
  k_t)``; ``S_t = S' + k_t u_t^T``; ``o_t = S_t^T q_t``; output
  ``(rms_head(o) * silu(z)) @ W_o`` with ``rms_head`` a plain RMSNorm over a
  head with weight ``w``.
- Gated attention: ``[q, gate] = x @ W_q`` per head, ``k, v = x @ W_k, x @
  W_v``; ``q = rms(q)``, ``k = rms(k)`` over the head; rotate-half rotary on
  the first ``partial_rotary_factor`` of the head; causal softmax at
  ``1/sqrt(head_dim)``; ``(attn * sigmoid(gate)) @ W_o``.
- Routed feed-forward: ``p = softmax(x @ W_r)`` over all experts; the
  ``num_experts_per_tok`` largest; weights ``p_e / sum of those``;
  ``routed = sum_e w_e E_e(x)`` over the chosen experts THAT ARE HELD, with
  ``E(x) = (silu(x @ W_gate) * (x @ W_up)) @ W_down``; ``shared = sigmoid(x @
  w_s) * E_shared(x)``; ``moe(x) = routed + shared``.

Departures from the published code, none of which changes a shape: the
columns of ``W_qkvz`` and ``W_ba`` are laid out ``[q | k | v | z]`` and ``[b |
a]`` and not interleaved per key head (a permutation of random columns); the
multi-token-prediction head is left out; the l2norm's epsilon is 1e-6 under
the root, as the family's kernels have it.
"""
import functools

import jax
import jax.numpy as jnp

SCAN_BLOCK = 64       # positions per checkpointed block of a recurrence
TOKEN_BLOCK = 1024    # tokens per checkpointed block of the feed-forward


def layer_kinds(cfg):
    return ["full_attention"
            if (i + 1) % cfg["full_attention_interval"] == 0
            else "linear_attention"
            for i in range(cfg["num_hidden_layers"])]


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def delta_rule_recurrent(q, k, v, g, beta):
    """One head: ``q, k`` ``[S, d_k]``, ``v`` ``[S, d_v]``, ``g, beta``
    ``[S]``; returns ``o`` ``[S, d_v]``.  A scan over positions, in blocks
    whose inner steps are recomputed in the backward pass."""
    s = q.shape[0]
    block = min(SCAN_BLOCK, s)
    pad = -s % block
    xs = [jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
          for x in (q, k, v, g, beta)]          # padded: beta = 0, g = 0
    xs = [x.reshape((-1, block) + x.shape[1:]) for x in xs]

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t) * state
        u = b_t * (v_t - state.T @ k_t)
        state = state + jnp.outer(k_t, u)
        return state, state.T @ q_t

    @jax.checkpoint
    def run_block(state, x):
        return jax.lax.scan(step, state, x)

    zero = jnp.zeros((q.shape[1], v.shape[1]), jnp.float32)
    _, o = jax.lax.scan(run_block, zero, tuple(xs))
    return o.reshape((-1, v.shape[1]))[:s]


def gated_delta_net(p, x, cfg):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    n_k, n_v = hk * dk, hv * dv
    s = x.shape[0]
    qkvz = x @ p["qkvz"]
    qkv, z = qkvz[:, :2 * n_k + n_v], qkvz[:, 2 * n_k + n_v:]
    ba = x @ p["ba"]
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    width = cfg["linear_conv_kernel_dim"]
    padded = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    conv = jnp.zeros_like(qkv)
    for i in range(width):      # y_t = sum_i w_i x_{t - (width - 1) + i}
        conv = conv + padded[i:i + s] * p["conv"][i]
    qkv = jax.nn.silu(conv)
    q = qkv[:, :n_k].reshape(s, hk, dk)
    k = qkv[:, n_k:2 * n_k].reshape(s, hk, dk)
    v = qkv[:, 2 * n_k:].reshape(s, hv, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / dk ** 0.5
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    rep = hv // hk             # value head h reads key head h // rep
    o = jax.vmap(delta_rule_recurrent, in_axes=1, out_axes=1)(
        jnp.repeat(q, rep, axis=1), jnp.repeat(k, rep, axis=1), v, g,
        beta)                                           # [S, H_v, d_v]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * p["norm"]
    o = o * jax.nn.silu(z.reshape(s, hv, dv))
    return o.reshape(s, n_v) @ p["out"]


def rotate_half_rotary(x, theta):
    """``x`` ``[S, H, R]``: pairs ``(d, d + R/2)`` turned by ``pos *
    theta^(-2d/R)``."""
    s, _, r = x.shape
    freqs = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) / (r // 2))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def gated_attention(p, x, cfg):
    h, h_kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    s = x.shape[0]
    qg = (x @ p["q"]).reshape(s, h, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = (x @ p["k"]).reshape(s, h_kv, hd)
    v = (x @ p["v"]).reshape(s, h_kv, hd)
    q = rms(q, p["q_norm"], cfg["rms_norm_eps"])
    k = rms(k, p["k_norm"], cfg["rms_norm_eps"])
    rot = int(hd * cfg["partial_rotary_factor"])
    q = jnp.concatenate(
        [rotate_half_rotary(q[..., :rot], cfg["rope_theta"]), q[..., rot:]],
        -1)
    k = jnp.concatenate(
        [rotate_half_rotary(k[..., :rot], cfg["rope_theta"]), k[..., rot:]],
        -1)
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
    y = jnp.moveaxis(heads, 0, 1) * jax.nn.sigmoid(gate)
    return y.reshape(s, h * hd) @ p["out"]


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def routed_feed_forward(p, x, cfg):
    """Returns ``(moe(x), assignments to each held expert)``."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    first = cfg.get("first_expert", 0)

    def add_expert(routed, expert):       # one held expert, all the tokens
        e, w_gate, w_up, w_down = expert
        mine = top_i == first + e
        w_e = jnp.sum(jnp.where(mine, top_p, 0.0), axis=-1)
        return (routed + w_e[:, None] * swiglu(x, w_gate, w_up, w_down),
                jnp.sum(mine))

    routed, counts = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.arange(p["gate"].shape[0]), p["gate"], p["up"], p["down"]))
    shared = jax.nn.sigmoid(x @ p["shared_router"]) * swiglu(
        x, p["shared_gate"], p["shared_up"], p["shared_down"])
    return routed + shared, counts


def block(p, x, kind, cfg):
    y = rms(x, p["norm_1"]["w"], cfg["rms_norm_eps"])
    if kind == "full_attention":
        x = x + gated_attention(p["attn"], y, cfg)
    else:
        x = x + gated_delta_net(p["gdn"], y, cfg)
    # position-wise, so in blocks of tokens whose intermediates (every held
    # expert's output for every token) are computed again going backward
    y = rms(x, p["norm_2"]["w"], cfg["rms_norm_eps"])
    rows = y.shape[0] if y.shape[0] % TOKEN_BLOCK else TOKEN_BLOCK
    y, counts = jax.lax.map(
        jax.checkpoint(lambda t: routed_feed_forward(p["moe"], t, cfg)),
        y.reshape(-1, rows, y.shape[1]))
    return x + y.reshape(x.shape), jnp.sum(counts, axis=0)


def hidden_states(params, tokens, cfg):
    """``tokens`` ``[S]`` -> the normed last hidden states ``[S, hidden]``
    and the held experts' assignment counts ``[layers, experts_held]``."""
    x = params["embed"][tokens]
    counts = []
    for i, kind in enumerate(layer_kinds(cfg)):
        x, c = jax.checkpoint(
            functools.partial(block, kind=kind, cfg=cfg))(
                params[f"l_{i}"], x)
        counts.append(c)
    return rms(x, params["norm"]["w"], cfg["rms_norm_eps"]), \
        jnp.stack(counts)


def loss(params, batch, cfg):
    """Mean next-token cross entropy over a batch ``{"tokens", "targets"}``
    of ``[B, S]``, one sequence at a time, under
    ``jax.default_matmul_precision("highest")``."""
    with jax.default_matmul_precision("highest"):
        def one(tokens, targets):
            h, _ = hidden_states(params, tokens, cfg)
            logp = jax.nn.log_softmax(h @ params["lm_head"], axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, targets[:, None], axis=-1))

        per = jax.lax.map(lambda b: one(*b),
                          (batch["tokens"], batch["targets"]))
        return jnp.mean(per)


def train_steps(params, batches, cfg, optimizer, micro_batches=1):
    """Losses of plain training steps on ``batches`` from a copy of
    ``params``: ``value_and_grad`` of ``loss`` over ``micro_batches`` equal
    parts of a batch (gradients averaged), then one optimizer update.
    Returns ``(losses, params after the last step)``."""
    import optax

    def split(b):
        return jax.tree.map(
            lambda x: x.reshape((micro_batches, -1) + x.shape[1:]), b)

    def step(p, s, b):
        def body(acc, one):
            out = jax.value_and_grad(loss)(p, one, cfg)
            return jax.tree.map(jnp.add, acc, out), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        (total, grads), _ = jax.lax.scan(body, zero, split(b))
        grads = jax.tree.map(lambda g: g / micro_batches, grads)
        updates, s = optimizer.update(grads, s, p)
        return optax.apply_updates(p, updates), s, total / micro_batches

    def start(p):       # a copy to donate: the caller keeps its weights
        p = jax.tree.map(jnp.copy, p)
        return p, optimizer.init(p)

    jstep = jax.jit(step, donate_argnums=(0, 1))
    p, s = jax.jit(start)(params)
    losses = []
    for b in batches:
        p, s, value = jstep(p, s, jax.tree.map(jnp.asarray, b))
        losses.append(float(value))
    return losses, p
