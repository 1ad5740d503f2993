"""LFM2 family: a chip's share of the short-convolution / sparse decoder
(``model_type: lfm2_moe``), trained on a seeded corpus read through the
loader.

The configuration file carries the model's ``config.json`` keys
(``hidden_size``, ``layer_types``, ``num_dense_layers``, ``num_experts`` ...)
with the cuts listed under ``reduced``, the deployment they stand for, and
under ``assumed`` what the config leaves to the code and the training set-up.
``layer_types`` is kept whole.  ``num_hidden_layers`` and ``num_dense_layers``
count the layers kept HERE: the first ``num_dense_layers`` of the published
dense layers (leading dense layers count once), then the layers that follow
the published dense ones (``layers_here``).  ``num_experts`` is the count
held HERE (experts ``first_expert ...``); the router keeps
``deployment.num_experts_published`` outputs.  The corpus writer is the
Qwen3-Next family's, the stream the GPT family's and the calibration tokens
the Nemotron-H family's.

The seeded weights carry a selection bias that balances the routing
(``balancing_bias``), as the Nemotron-H family's do and for its reason: the
program never moves that bias and the published config has no rule for
moving it, but balancing is what it is there for, and with it at zero the
rows on the 8 held experts are a seed's lottery that the step time follows
(PERF.md section 4c has the readings).

Below the family's own code is a copy of the plain float32 reference,
``tests/lfm2_reference.py`` (``benchmark/tests`` holds the two together), so
that the yardstick imports nothing of the program but what it measures.
"""
import functools
import os

import numpy as np

from benchmark.harness import cells

UNIT = "tokens"


def layers_here(config):
    """The published indices of the layers kept: the leading dense layers
    kept, then the layers after all the published dense ones."""
    dense, dense_published = (config["num_dense_layers"],
                              config["deployment"]["num_dense_layers_published"])
    routed = config["num_hidden_layers"] - dense
    return tuple(range(dense)) + tuple(
        range(dense_published, dense_published + routed))


def model_config(config, cell):
    import jax.numpy as jnp

    from autodist_tpu.models.lfm2 import Lfm2Config

    a, d = config["assumed"], config["deployment"]
    return Lfm2Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        layer_types=tuple(config["layer_types"]),
        num_dense_layers=d["num_dense_layers_published"],
        layers_here=layers_here(config),
        num_heads=config["num_attention_heads"],
        num_kv_heads=config["num_key_value_heads"],
        rope_theta=config["rope_theta"],
        conv_L_cache=config["conv_L_cache"],
        intermediate_size=config["intermediate_size"],
        num_experts=d["num_experts_published"],
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        use_expert_bias=config["use_expert_bias"],
        first_expert=d["first_expert"],
        experts_held=config["num_experts"],
        rows_bound=cell.get("moe_rows_bound"),
        norm_eps=config["norm_eps"],
        dtype=jnp.dtype(a["compute_dtype"]).type,
        attention_impl=a["attention_impl"], remat=a["remat"])


def reference_config(config):
    """The reference's plain dict: the published keys, ``layer_types`` cut to
    the layers kept, and the share."""
    return {**{k: v for k, v in config.items()
               if isinstance(v, (int, float, bool))},
            "layer_types": tuple(config["layer_types"][i]
                                 for i in layers_here(config)),
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
        # the rate climbs to its value over the warm-up, as a run's does
        # (the Nemotron-H family says what happens to a random router's
        # balance at the full rate from the first step)
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

        from autodist_tpu.models.train_lib import lfm2_capture
        from autodist_tpu.utils.rng import host_key

        def init(key, tokens):
            loss_fn, params, sparse = lfm2_capture(
                self.cfg, self.seq_len, rng=key)
            self.loss_fn = loss_fn
            self.distribute_kwargs["sparse_vars"] = sparse
            return {**params, **balancing_bias(
                params, tokens, reference_config(self.config),
                self.cfg.num_experts)}

        # the seed's tokens go in as an argument: as a constant of the
        # program every seed would compile its own.  Kept on the host: the
        # reference and the session each put their own copy on the chip,
        # and never both at once
        return jax.device_get(jax.jit(init)(
            host_key(self.seed),
            cells.load_family("nemotron_h").calibration_tokens(
                self.cell, self.cfg, self.seed)))

    def flops_per_unit(self, params):
        """Model FLOPs per token from the real parameter tree: a held
        expert is counted at ``top_k / experts`` of the tokens, the tied
        embedding once, as the head."""
        import jax

        from benchmark.harness.lfm2_cost import train_flops_per_token

        n_dense = n_experts = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
            if leaf.ndim == 3:
                n_experts += int(np.prod(leaf.shape))
            elif leaf.ndim == 2 and "'conv'" not in jax.tree_util.keystr(path):
                n_dense += int(np.prod(leaf.shape))     # the taps: below
        c = self.cfg
        mixers = [m for m, _ in c.layer_kinds]
        return train_flops_per_token(
            n_dense, n_experts, c.num_experts_per_tok, c.num_experts,
            self.seq_len, mixers.count("full_attention"), c.hidden_size,
            mixers.count("conv"), c.hidden_size, c.conv_L_cache)

    def reference_losses(self, params, batches, device):
        """Losses of the plain float32 reference's training steps on
        ``batches`` from a copy of ``params`` (below: shifted sums, masked
        softmax, a loop over the held experts,
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


def balancing_bias(params, tokens, cfg, experts_total):
    """``{layer: {..., "expert_bias": b}}`` for every routed layer: ``b_e``
    is minus the score that expert ``e`` exceeds on ``k`` of every
    ``experts_total`` of the calibration tokens (centred), so that with it
    every expert passes a common threshold equally often and the ``k``
    chosen a token spread evenly over the experts.  One forward pass of the
    plain reference below, layer after layer, each routed layer run with
    the bias just made for it."""
    k = cfg["num_experts_per_tok"]
    x = params["embed"][tokens]
    made = {}
    for j, mixer in enumerate(cfg["layer_types"]):
        p, dense = params[f"l_{j}"], j < cfg["num_dense_layers"]
        x = jax.vmap(lambda t: mixed(p, t, mixer, cfg))(x)
        if not dense:
            y = rms(x, p["ffn_norm"]["w"], cfg["norm_eps"])
            scores = jax.nn.sigmoid(y @ p["moe"]["router"])
            b = -jnp.quantile(scores.reshape(-1, experts_total),
                              1.0 - k / experts_total, axis=0)
            p = {**p, "moe": {**p["moe"], "expert_bias": b - jnp.mean(b)}}
            made[f"l_{j}"] = p
        x = jax.vmap(lambda t: feed_forward(p, t, dense, cfg)[0])(x)
    return made


def layer_shapes(cell, config):
    """What the per-layer readers need of the model's shapes."""
    kinds = reference_config(config)["layer_types"]
    return {"batch_per_chip": cell["batch"] // cell["chips"],
            "seq_len": cell["seq_len"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"]
            // config["num_attention_heads"],
            "sconv_layers": kinds.count("conv"),
            "sconv_channels": config["hidden_size"],
            "sconv_taps": config["conv_L_cache"],
            "moe_layers": config["num_hidden_layers"]
            - config["num_dense_layers"],
            "experts_held": config["num_experts"],
            "hidden": config["hidden_size"],
            "expert_width": config["moe_intermediate_size"]}


# ---------------------------------------------------------------------------
# The plain reference: a copy of tests/lfm2_reference.py below its imports
# (its docstring there has the equations and the departures).
# ---------------------------------------------------------------------------

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

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
