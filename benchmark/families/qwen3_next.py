"""Qwen3-Next family: a chip's share of the hybrid sparse decoder, trained on
a seeded corpus read through the loader.

The configuration file carries the model's ``config.json`` keys
(``hidden_size``, ``num_hidden_layers``, ``linear_*``, ``num_experts`` ...)
with the cuts listed under ``reduced``, the deployment they stand for, and
under ``assumed`` what the config leaves to the code and the training
set-up.  ``num_experts`` is the count held HERE (experts
``first_expert ...``); the router keeps ``deployment.num_experts_published``
outputs.  The stream is the GPT family's.  Below the family's own code is a
copy of the plain float32 reference, ``tests/qwen3_next_reference.py``
(``benchmark/tests`` holds the two together), so that the yardstick imports
nothing of the program but what it measures.
"""
import functools
import os

import numpy as np

from benchmark.harness import cells

UNIT = "tokens"


def model_config(config, cell):
    import jax.numpy as jnp

    from autodist_tpu.models.qwen3_next import Qwen3NextConfig

    a, d = config["assumed"], config["deployment"]
    return Qwen3NextConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=float(config["rope_theta"]),
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        chunk_size=a["delta_rule_chunk"],
        num_experts=d["num_experts_published"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        first_expert=d["first_expert"], experts_held=config["num_experts"],
        rows_bound=cell.get("moe_rows_bound"),
        rms_norm_eps=config["rms_norm_eps"],
        dtype=jnp.dtype(a["compute_dtype"]).type,
        attention_impl=a["attention_impl"], remat=a["remat"])


def reference_config(config):
    """The reference's plain dict: the published keys and the share."""
    return {**{k: v for k, v in config.items()
               if isinstance(v, (int, float, bool))},
            "first_expert": config["deployment"]["first_expert"]}


def write_token_corpus(path, n_records, seq_len, vocab_size, seed, offset):
    """A seeded corpus whose token ``r`` (by rank) has probability
    proportional to ``1 / (r + offset)``.  With the offset no id is frequent
    enough for its ten experts to decide how many rows land on the held
    ones, and the unigram distribution still spans ``1 + vocab/offset`` to
    1, so it can be learnt and a falling loss means the update was
    applied."""
    from autodist_tpu.data.loader import write_records

    r = np.random.RandomState(seed)
    p = 1.0 / (np.arange(vocab_size) + float(offset))
    toks = r.choice(vocab_size, size=(n_records, seq_len + 1), p=p / p.sum())
    write_records(path, toks.astype(np.int32))


class Job:
    """One cell's training job, as the harness drives it."""

    unit = UNIT

    def __init__(self, cell, config, seed, work_dir):
        import optax

        self.cell, self.config, self.seed = cell, config, seed
        self.cfg = model_config(config, cell)
        self.seq_len = cell["seq_len"]
        self.units_per_step = cell["batch"] * self.seq_len
        self.optimizer = optax.adamw(config["assumed"]["learning_rate"])
        self.distribute_kwargs = {"has_aux": True}
        corpus = os.path.join(work_dir, "corpus.bin")
        write_token_corpus(corpus, cell["feed"]["records"], self.seq_len,
                           self.cfg.vocab_size, seed,
                           cell["feed"]["rank_offset"])
        self.stream = cells.load_family("gpt").TokenStream(
            corpus, self.seq_len, cell["batch"], seed, cell["feed"])
        self.loss_fn = None

    def make_params(self):
        """The seeded weights, made on the device in one jitted call."""
        import jax

        from autodist_tpu.models.train_lib import qwen3_next_capture
        from autodist_tpu.utils.rng import host_key

        def init(key):
            loss_fn, params, sparse = qwen3_next_capture(
                self.cfg, self.seq_len, rng=key)
            self.loss_fn = loss_fn
            self.distribute_kwargs["sparse_vars"] = sparse
            return params

        # kept on the host: the reference and the session each put their own
        # copy on the chip, and never both at once
        return jax.device_get(jax.jit(init)(host_key(self.seed)))

    def flops_per_unit(self, params):
        """Model FLOPs per token from the real parameter tree: a held
        expert is counted at ``top_k / experts`` of the tokens."""
        import jax

        from benchmark.harness.qwen3_next_cost import train_flops_per_token

        n_dense = n_experts = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            name = jax.tree_util.keystr(path)
            if leaf.ndim == 3:
                n_experts += int(np.prod(leaf.shape))
            elif leaf.ndim == 2 and "embed" not in name:
                n_dense += int(np.prod(leaf.shape))
        c = self.cfg
        kinds = c.layer_types
        return train_flops_per_token(
            n_dense, n_experts, c.num_experts_per_tok, c.num_experts,
            self.seq_len, kinds.count("full_attention"), c.num_heads,
            c.head_dim, kinds.count("linear_attention"),
            c.linear_num_value_heads, c.linear_key_head_dim,
            c.linear_value_head_dim)

    def reference_losses(self, params, batches, device):
        """Losses of the plain float32 reference's training steps on
        ``batches`` from a copy of ``params`` (below: recurrences over
        positions, masked softmax, a loop over the held experts,
        ``jax.default_matmul_precision("highest")``), each batch in
        ``reference.micro_batches`` parts whose gradients are averaged."""
        import jax

        # weights and batches go in as host arrays, so every argument of both
        # steps is uncommitted to a device and the second call finds the
        # first's executable (a committed batch made the second step's
        # weights committed: a second 47 MB cache entry and a second minute)
        with jax.default_device(device):
            losses, _ = train_steps(
                params, batches, reference_config(self.config),
                self.optimizer,
                micro_batches=self.cell["reference"]["micro_batches"])
        return losses

    def close(self):
        self.stream.close()


def layer_shapes(cell, config):
    """What the per-layer readers need of the model's shapes."""
    layers = config["num_hidden_layers"]
    full = layers // config["full_attention_interval"]
    return {"batch_per_chip": cell["batch"] // cell["chips"],
            "seq_len": cell["seq_len"], "layers": full,
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "gdn_layers": layers - full,
            "gdn_key_heads": config["linear_num_key_heads"],
            "gdn_value_heads": config["linear_num_value_heads"],
            "gdn_key_dim": config["linear_key_head_dim"],
            "gdn_value_dim": config["linear_value_head_dim"],
            "moe_layers": layers, "experts_held": config["num_experts"],
            "hidden": config["hidden_size"],
            "expert_width": config["moe_intermediate_size"]}


# ---------------------------------------------------------------------------
# The plain reference: a copy of tests/qwen3_next_reference.py below its
# imports (its docstring there has the equations and the departures).
# ---------------------------------------------------------------------------

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

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
