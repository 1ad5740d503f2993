"""Nemotron-H family: a chip's share of the hybrid state-space / sparse
decoder, trained on a seeded corpus read through the loader.

The configuration file carries the model's ``config.json`` keys
(``hidden_size``, ``hybrid_override_pattern``, ``mamba_*``,
``n_routed_experts`` ...) with the cuts listed under ``reduced``, the deployment they stand for,
and under ``assumed`` what the config leaves to the code and the training
set-up.  ``num_hidden_layers`` is the count of layers kept HERE: the first
that many characters of the pattern, which the file keeps whole.
``n_routed_experts`` is the count held HERE (experts ``first_expert ...``);
the router keeps ``deployment.n_routed_experts_published`` outputs.  The
corpus writer is the Qwen3-Next family's and the stream the GPT family's.

The seeded weights carry a selection bias that balances the routing
(``balancing_bias``).  The program never moves that bias and the published
config has no rule for moving it, but balancing is what it is there for: at
zero, under random weights, ``relu(.)^2`` gives every routed layer's output a
component all tokens share, the next routers' scores inherit it, and a
handful of the 128 experts take a quarter of the tokens each; which of them
are among the 8 held here is the seed's lottery (PERF.md section 4b has the
readings).  A deployment's trained bias does not leave it so.

Below the family's own code is a copy of the plain float32 reference,
``tests/nemotron_h_reference.py`` (``benchmark/tests`` holds the two
together), so that the yardstick imports nothing of the program but what it
measures.
"""
import functools
import os

import numpy as np

from benchmark.harness import cells

UNIT = "tokens"


def pattern_here(config):
    return config["hybrid_override_pattern"][:config["num_hidden_layers"]]


def model_config(config, cell):
    import jax.numpy as jnp

    from autodist_tpu.models.nemotron_h import NemotronHConfig

    a, d = config["assumed"], config["deployment"]
    return NemotronHConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        pattern=pattern_here(config),
        num_hidden_layers=d["num_hidden_layers_published"],
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_num_heads=config["mamba_num_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        n_groups=config["n_groups"],
        ssm_state_size=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        time_step_min=config["time_step_min"],
        time_step_max=config["time_step_max"],
        time_step_floor=config["time_step_floor"],
        n_routed_experts=d["n_routed_experts_published"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        first_expert=d["first_expert"],
        experts_held=config["n_routed_experts"],
        rows_bound=cell.get("moe_rows_bound"),
        norm_eps=config["layer_norm_epsilon"],
        dtype=jnp.dtype(a["compute_dtype"]).type,
        attention_impl=a["attention_impl"], remat=a["remat"])


def reference_config(config):
    """The reference's plain dict: the published keys, the pattern cut to
    the layers kept, and the share."""
    return {**{k: v for k, v in config.items()
               if isinstance(v, (int, float, bool))},
            "hybrid_override_pattern": pattern_here(config),
            "first_expert": config["deployment"]["first_expert"]}


class Job:
    """One cell's training job, as the harness drives it."""

    unit = UNIT

    def __init__(self, cell, config, seed, work_dir):
        import optax

        self.cell, self.config, self.seed = cell, config, seed
        self.cfg = model_config(config, cell)
        self.seq_len = cell["seq_len"]
        self.units_per_step = cell["batch"] * self.seq_len
        # the rate climbs to its value over the warm-up, as a run's does: at
        # the full rate from the first step AdamW moves every router weight
        # by the rate a step, and under random weights the routing collapses
        # within ten steps (PERF.md section 4b)
        a = config["assumed"]
        self.optimizer = optax.adamw(optax.linear_schedule(
            a["learning_rate"] / a["warmup_steps"], a["learning_rate"],
            a["warmup_steps"]))
        self.distribute_kwargs = {"has_aux": True}
        corpus = os.path.join(work_dir, "corpus.bin")
        cells.load_family("qwen3_next").write_token_corpus(
            corpus, cell["feed"]["records"], self.seq_len,
            self.cfg.vocab_size, seed, cell["feed"]["rank_offset"])
        self.stream = cells.load_family("gpt").TokenStream(
            corpus, self.seq_len, cell["batch"], seed, cell["feed"])
        self.loss_fn = None

    def make_params(self):
        """The seeded weights, made on the device in one jitted call."""
        import jax

        from autodist_tpu.models.train_lib import nemotron_h_capture
        from autodist_tpu.utils.rng import host_key

        def init(key, tokens):
            loss_fn, params, sparse = nemotron_h_capture(
                self.cfg, self.seq_len, rng=key)
            self.loss_fn = loss_fn
            self.distribute_kwargs["sparse_vars"] = sparse
            return {**params, **balancing_bias(
                params, tokens, reference_config(self.config),
                self.cfg.n_routed_experts)}

        # the seed's tokens go in as an argument: as a constant of the
        # program every seed would compile its own.  Kept on the host: the
        # reference and the session each put their own copy on the chip,
        # and never both at once
        return jax.device_get(jax.jit(init)(
            host_key(self.seed),
            calibration_tokens(self.cell, self.cfg, self.seed)))

    def flops_per_unit(self, params):
        """Model FLOPs per token from the real parameter tree: a held
        expert is counted at ``top_k / experts`` of the tokens."""
        import jax

        from benchmark.harness.nemotron_h_cost import train_flops_per_token

        n_dense = n_experts = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            name = jax.tree_util.keystr(path)
            if leaf.ndim == 3:
                n_experts += int(np.prod(leaf.shape))
            elif leaf.ndim == 2 and "embed" not in name:
                n_dense += int(np.prod(leaf.shape))
        c = self.cfg
        kinds = c.layer_kinds
        return train_flops_per_token(
            n_dense, n_experts, c.num_experts_per_tok, c.n_routed_experts,
            self.seq_len, kinds.count("attn"), c.num_heads, c.head_dim,
            kinds.count("ssd"), c.mamba_num_heads, c.mamba_head_dim,
            c.ssm_state_size)

    def reference_losses(self, params, batches, device):
        """Losses of the plain float32 reference's training steps on
        ``batches`` from a copy of ``params`` (below: the recurrence over
        positions, masked softmax, a loop over the held experts,
        ``jax.default_matmul_precision("highest")``), each batch in
        ``reference.micro_batches`` parts whose gradients are averaged."""
        import jax

        # weights and batches go in as host arrays, so every argument of both
        # steps is uncommitted to a device and the second call finds the
        # first's executable (families/qwen3_next.py has the price of not)
        with jax.default_device(device):
            losses, _ = train_steps(
                params, batches, reference_config(self.config),
                self.optimizer,
                micro_batches=self.cell["reference"]["micro_batches"])
        return losses

    def close(self):
        self.stream.close()


def calibration_tokens(cell, cfg, seed):
    """Two sequences of the corpus's distribution (ids by rank, probability
    proportional to ``1 / (rank + rank_offset)``), drawn apart from it."""
    r = np.random.RandomState((seed + 1) % (2 ** 31 - 1))
    p = 1.0 / (np.arange(cfg.vocab_size) + float(cell["feed"]["rank_offset"]))
    return r.choice(cfg.vocab_size, size=(2, cell["seq_len"]),
                    p=p / p.sum()).astype(np.int32)


def balancing_bias(params, tokens, cfg, experts_total):
    """``{layer: {..., "router_bias": b}}`` for every routed layer: ``b_e``
    is minus the score that expert ``e`` exceeds on ``k`` of every
    ``experts_total`` of the calibration tokens (centred), so that with it
    every expert passes a common threshold equally often and the ``k``
    chosen a token spread evenly over the experts.  One forward pass of the
    plain reference below, layer after layer, each routed layer run with
    the bias just made for it."""
    k = cfg["num_experts_per_tok"]
    x = params["embed"][tokens]
    made = {}
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        p = params[f"l_{i}"]
        if kind == "E":
            y = rms(x, p["norm"]["w"], cfg["layer_norm_epsilon"])
            scores = jax.nn.sigmoid(y @ p["moe"]["router"])
            b = -jnp.quantile(scores.reshape(-1, experts_total),
                              1.0 - k / experts_total, axis=0)
            p = {**p, "moe": {**p["moe"], "router_bias": b - jnp.mean(b)}}
            made[f"l_{i}"] = p
        x = jax.vmap(lambda t: block(p, t, kind, cfg)[0])(x)
    return made


def layer_shapes(cell, config):
    """What the per-layer readers need of the model's shapes."""
    kinds = pattern_here(config)
    return {"batch_per_chip": cell["batch"] // cell["chips"],
            "seq_len": cell["seq_len"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "ssd_layers": kinds.count("M"),
            "ssd_heads": config["mamba_num_heads"],
            "ssd_head_dim": config["mamba_head_dim"],
            "ssd_groups": config["n_groups"],
            "ssd_state": config["ssm_state_size"],
            "relu2_layers": kinds.count("E"),
            "experts_held": config["n_routed_experts"],
            "hidden": config["hidden_size"],
            "expert_width": config["moe_intermediate_size"]}


# ---------------------------------------------------------------------------
# The plain reference: a copy of tests/nemotron_h_reference.py below its
# imports (its docstring there has the equations and the departures).
# ---------------------------------------------------------------------------

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

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
