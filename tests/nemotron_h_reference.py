"""Plain float32 reference of Nemotron-H's language model, for training.

Straightforward ``jax.numpy``: the state-space recurrence as a ``lax.scan``
over positions (checkpointed in blocks of positions so that long sequences
fit), attention as a masked softmax, one head after another, the experts as a
loop over the held experts with a mask.  No kernels, no chunks, no packing,
nothing from ``autodist_tpu``.  ``benchmark/families/nemotron_h.py`` holds a
copy of everything below the imports (``benchmark/tests`` checks that the two
agree), so that the yardstick imports nothing a later PR changes.

It reads the parameter tree of ``autodist_tpu/models/nemotron_h.py``
(``l_<i>/{norm, ssd | attn | moe}``, ``embed``, ``norm``, ``lm_head``) and a
configuration as a plain dict ``cfg`` with the published keys of
``config.json`` (``hybrid_override_pattern`` cut to the layers kept) plus
``first_expert``; the experts held are those whose weights the tree has.

Equations, from ``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` (``model_type: nemotron_h``)
and the model type's published description (``x`` is ``[S, hidden]``):

- Norm: ``rms(x) = x * rsqrt(mean(x^2) + eps) * w``, ``eps`` 1e-5, ``w``
  initialised 1.
- Layer ``l`` of kind ``hybrid_override_pattern[l]``: ``x <- x +
  mixer(rms(x))``, one mixer and nothing else; a last norm, then ``logits =
  x @ W_head`` (untied).  No bias anywhere except the convolution's.
- ``M``, Mamba-2: ``d_inner = mamba_num_heads * mamba_head_dim`` (H heads of
  P), G = ``n_groups``, N = ``ssm_state_size``.  ``[z | xBC | dt] = x @
  W_in`` of widths ``d_inner | d_inner + 2 G N | H``; ``xBC = silu(causal
  depthwise conv1d(xBC, width conv_kernel) + b_conv)``, split ``[u | B |
  C]`` into H heads of P and G groups of N each; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)`` a head (no ``time_step_limit`` in the
  config: no clamp).  Head ``h`` reads the ``B, C`` of group ``h // (H /
  G)`` and keeps ``S`` of ``[P, N]``, from ``S_0 = 0``: ``S_t = exp(dt_t A)
  S_{t-1} + dt_t u_t B_t^T``; ``y_t = S_t C_t + D u_t``.  Then gate first,
  norm second: ``y = rms_group(y * silu(z)) * w`` with the mean square over
  each of the G runs of ``d_inner / G`` channels, and ``out = y @ W_out``.
- ``*``, attention: ``q = x @ W_q`` (``num_attention_heads`` of
  ``head_dim``), ``k, v = x @ W_k, x @ W_v`` (``num_key_value_heads``),
  causal softmax of ``q k^T / sqrt(head_dim)``, query head ``i`` reading K/V
  head ``i // (heads / kv_heads)``; ``out = . @ W_o``.  No rotary and no
  other position signal: ``nemotron_h``'s attention applies none (the Mamba
  layers carry position); the config's ``rope_theta`` and
  ``partial_rotary_factor`` are not read by that model type.
- ``E``, routed feed-forward: ``s = sigmoid(x @ W_r)`` over all
  ``n_routed_experts``; the ``num_experts_per_tok`` largest of ``s + b``
  (``b`` the selection bias; ``n_group = topk_group = 1``: no group limit);
  weights ``s`` at the chosen (without ``b``), divided by their sum ``+
  1e-20`` (``norm_topk_prob``), times ``routed_scaling_factor``; ``routed =
  sum_e w_e E_e(x)`` over the chosen experts THAT ARE HELD, with ``E(x) =
  relu(x @ W_up)^2 @ W_down`` (``mlp_hidden_act: relu2``, two matrices);
  the shared expert has the same form and is added ungated.

Departures from the published code, none of which changes a shape: ``b`` gets
no gradient and the config gives no rule for updating it, so it stays as it
starts; multi-token prediction is absent (the config has none).
"""
import functools

import jax
import jax.numpy as jnp

SCAN_BLOCK = 64       # positions per checkpointed block of the recurrence
TOKEN_BLOCK = 1024    # tokens per checkpointed block of the feed-forward


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def ssm_recurrent(u, dt, a, b, c, d):
    """One sequence: ``u`` ``[S, H, P]``, ``dt`` ``[S, H]``, ``b, c`` ``[S,
    G, N]``, ``a, d`` ``[H]``; returns ``y`` ``[S, H, P]``.  A scan over
    positions with every head's state ``[H, P, N]``, in blocks whose inner
    steps are recomputed in the backward pass."""
    s, h, p = u.shape
    rep = h // b.shape[1]       # head i reads group i // rep
    block = min(SCAN_BLOCK, s)
    pad = -s % block
    xs = [jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
          for x in (u, dt, b, c)]               # padded: dt = 0
    xs = [x.reshape((-1, block) + x.shape[1:]) for x in xs]

    def step(state, x):
        u_t, dt_t, b_t, c_t = x
        b_t, c_t = jnp.repeat(b_t, rep, axis=0), jnp.repeat(c_t, rep, axis=0)
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * u_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t) + d[:, None] * u_t

    @jax.checkpoint
    def run_block(state, x):
        return jax.lax.scan(step, state, x)

    zero = jnp.zeros((h, p, b.shape[2]), jnp.float32)
    _, y = jax.lax.scan(run_block, zero, tuple(xs))
    return y.reshape((-1, h, p))[:s]


def mamba2(p, x, cfg):
    h, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, s = h * hd, x.shape[0]
    zxbcdt = x @ p["in"]
    z, xbc = zxbcdt[:, :inner], zxbcdt[:, inner:2 * inner + 2 * g * n]
    dt = jax.nn.softplus(zxbcdt[:, 2 * inner + 2 * g * n:] + p["dt_bias"])
    width = cfg["conv_kernel"]
    padded = jnp.pad(xbc, ((width - 1, 0), (0, 0)))
    conv = jnp.zeros_like(xbc) + p["conv_bias"]
    for i in range(width):      # y_t = sum_i w_i x_{t - (width - 1) + i}
        conv = conv + padded[i:i + s] * p["conv"][i]
    xbc = jax.nn.silu(conv)
    y = ssm_recurrent(
        xbc[:, :inner].reshape(s, h, hd), dt, -jnp.exp(p["A_log"]),
        xbc[:, inner:inner + g * n].reshape(s, g, n),
        xbc[:, inner + g * n:].reshape(s, g, n), p["D"])
    y = (y.reshape(s, inner) * jax.nn.silu(z)).reshape(s, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    return (y.reshape(s, inner) * p["norm"]) @ p["out"]


def attention(p, x, cfg):
    h, h_kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    s = x.shape[0]
    q = (x @ p["q"]).reshape(s, h, hd)
    k = (x @ p["k"]).reshape(s, h_kv, hd)
    v = (x @ p["v"]).reshape(s, h_kv, hd)
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


def expert(x, w_up, w_down):
    return relu2(x @ w_up) @ w_down


def routed_feed_forward(p, x, cfg):
    """Returns ``(moe(x), assignments to each held expert)``."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(x @ p["router"])
    _, top_i = jax.lax.top_k(scores + p["router_bias"], k)
    top_s = jnp.take_along_axis(scores, top_i, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20)
    top_s = top_s * cfg["routed_scaling_factor"]
    first = cfg.get("first_expert", 0)

    def add_expert(routed, held):         # one held expert, all the tokens
        e, w_up, w_down = held
        mine = top_i == first + e
        w_e = jnp.sum(jnp.where(mine, top_s, 0.0), axis=-1)
        return (routed + w_e[:, None] * expert(x, w_up, w_down),
                jnp.sum(mine))

    routed, counts = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.arange(p["up"].shape[0]), p["up"], p["down"]))
    return routed + expert(x, p["shared_up"], p["shared_down"]), counts


def block(p, x, kind, cfg):
    """``(x + mixer(rms(x)), the held experts' assignment counts or
    None)``."""
    y = rms(x, p["norm"]["w"], cfg["layer_norm_epsilon"])
    if kind == "M":
        return x + mamba2(p["ssd"], y, cfg), None
    if kind == "*":
        return x + attention(p["attn"], y, cfg), None
    # position-wise, so in blocks of tokens whose intermediates (every held
    # expert's output for every token) are computed again going backward
    rows = y.shape[0] if y.shape[0] % TOKEN_BLOCK else TOKEN_BLOCK
    y, counts = jax.lax.map(
        jax.checkpoint(lambda t: routed_feed_forward(p["moe"], t, cfg)),
        y.reshape(-1, rows, y.shape[1]))
    return x + y.reshape(x.shape), jnp.sum(counts, axis=0)


def hidden_states(params, tokens, cfg):
    """``tokens`` ``[S]`` -> the normed last hidden states ``[S, hidden]``
    and the held experts' assignment counts ``[routed layers,
    experts_held]``."""
    x = params["embed"][tokens]
    counts = []
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        x, c = jax.checkpoint(
            functools.partial(block, kind=kind, cfg=cfg))(
                params[f"l_{i}"], x)
        if c is not None:
            counts.append(c)
    return rms(x, params["norm"]["w"], cfg["layer_norm_epsilon"]), \
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
    Returns ``(losses, params after the last step)``.

    The optimizer's state waits on the host while a step's gradients are
    made: the device then holds weights, gradients and activations, or
    weights, gradients and moments, and never all of them (at the
    benchmark's size that is 10.7 GB where all of them are 15.6)."""
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
