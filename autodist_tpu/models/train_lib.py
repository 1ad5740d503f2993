"""Glue: wrap a flax model into the (loss_fn, params, ...) capture that
``AutoDist.distribute`` expects — the analog of the reference benchmark
harness's model-to-train-loop wiring (``examples/benchmark/imagenet.py``).
"""
import jax
import jax.numpy as jnp
import optax

from autodist_tpu.const import BATCH_MASK_KEY
from autodist_tpu.utils.rng import host_key


def softmax_cross_entropy(logits, labels, mask=None):
    """Mean cross-entropy; with ``mask`` (1.0 real / 0.0 pad, from the
    session's uneven-batch padding) a masked mean over real examples."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    per_ex = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return jnp.mean(per_ex)
    mask = mask.astype(per_ex.dtype)
    return jnp.sum(per_ex * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def classifier_capture(model, input_shape, rng=None, with_batch_stats=True):
    """Init a flax image classifier; returns (loss_fn, params, mutable_state).

    ``loss_fn`` follows the framework convention for models with mutable
    state: ``loss_fn(params, state, batch) -> (loss, new_state)``.
    """
    rng = rng if rng is not None else host_key(0)
    variables = model.init(rng, jnp.zeros((1,) + tuple(input_shape)), train=False)
    params = variables["params"]
    state = {k: v for k, v in variables.items() if k != "params"}

    if state and with_batch_stats:
        def loss_fn(p, s, batch):
            logits, new_s = model.apply(
                {"params": p, **s}, batch["image"], train=True,
                mutable=list(s.keys()))
            return softmax_cross_entropy(logits, batch["label"],
                                         batch.get(BATCH_MASK_KEY)), new_s

        return loss_fn, params, state

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["image"], train=True)
        return softmax_cross_entropy(logits, batch["label"],
                                     batch.get(BATCH_MASK_KEY))

    return loss_fn, params, None


def bert_capture(config, seq_len, rng=None):
    """Init BertForPreTraining; returns (loss_fn, params, sparse_vars).

    ``loss_fn(params, batch, rng)`` — dropout needs the per-device rng the
    framework threads with ``has_rng=True``.
    """
    from autodist_tpu.models.bert import BertForPreTraining, pretraining_loss

    rng = rng if rng is not None else host_key(0)
    model = BertForPreTraining(config)
    dummy = jnp.zeros((1, seq_len), jnp.int32)
    params = model.init(rng, dummy, deterministic=True)["params"]

    def loss_fn(p, batch, step_rng):
        mlm, nsp = model.apply(
            {"params": p}, batch["input_ids"],
            token_type_ids=batch.get("token_type_ids"),
            attention_mask=batch.get("attention_mask"),
            deterministic=False, rngs={"dropout": step_rng})
        return pretraining_loss(mlm, nsp, batch)

    # word_embeddings is tied to the MLM head -> its gradient is dense
    # (rows + projection term); no variable qualifies for the pure-sparse
    # path, matching the reference where tied IndexedSlices densify
    return loss_fn, params, []


def _positional_mask(targets, example_mask):
    """Per-example (B,) session mask -> per-position mask matching
    ``targets``; None stays None (ops/losses.py handles the -100 ignores)."""
    if example_mask is None:
        return None
    m = example_mask.reshape(
        example_mask.shape + (1,) * (targets.ndim - example_mask.ndim))
    return jnp.broadcast_to(m, targets.shape)


def gpt_capture(config, seq_len, rng=None, streaming_loss=False,
                loss_chunk=8192):
    """Init a GPT causal LM; returns (loss_fn, params, sparse_vars).

    ``loss_fn(params, batch, rng)`` with ``batch = {"tokens", "targets"}``
    (targets pre-shifted by the caller).  The tied embedding's gradient is
    dense, so no variable takes the sparse path (same as BERT).

    ``streaming_loss=True`` computes the cross entropy against the tied
    ``wte`` table WITHOUT materializing the (B, S, V) logits
    (``ops/losses.py``) — at GPT-2 vocab the logits are the largest single
    training allocation, so this is the memory lever that buys batch size.
    """
    from autodist_tpu.models.gpt import GPT, gpt_loss
    from autodist_tpu.ops.losses import streaming_softmax_xent

    rng = rng if rng is not None else host_key(0)
    model = GPT(config)
    dummy = jnp.zeros((1, seq_len), jnp.int32)
    # return_hidden at init: the param tree is identical (all params are
    # created before the early return) and init never materializes the
    # (1, S, V) logits the streaming path exists to avoid
    params = model.init(rng, dummy, deterministic=True,
                        return_hidden=streaming_loss)["params"]

    if streaming_loss:
        def loss_fn(p, batch, step_rng):
            hidden = model.apply(
                {"params": p}, batch["tokens"], deterministic=False,
                return_hidden=True, rngs={"dropout": step_rng})
            t = batch["targets"]
            return streaming_softmax_xent(
                hidden, p["wte"], t,
                valid=_positional_mask(t, batch.get(BATCH_MASK_KEY)),
                chunk=loss_chunk)
    else:
        def loss_fn(p, batch, step_rng):
            logits = model.apply(
                {"params": p}, batch["tokens"],
                deterministic=False, rngs={"dropout": step_rng})
            return gpt_loss(logits, batch["targets"],
                            batch.get(BATCH_MASK_KEY))

    return loss_fn, params, []


def llama_capture(config, seq_len, rng=None, streaming_loss=False,
                  loss_chunk=8192):
    """Init a Llama-family causal LM; returns (loss_fn, params, sparse_vars).

    The input embedding is UNTIED (separate lm_head), so its gradient is
    pure rows — it takes the sparse path (Parallax routes it like the
    reference's IndexedSlices; PartitionedPS can shard the table).

    ``streaming_loss=True`` streams the untied (D, V) head through
    ``ops/losses.py`` (native "dv" layout — no transpose copy) — no
    (B, S, V) logits allocation.
    """
    from autodist_tpu.models.llama import Llama, llama_loss
    from autodist_tpu.ops.losses import streaming_softmax_xent

    rng = rng if rng is not None else host_key(0)
    model = Llama(config)
    dummy = jnp.zeros((1, seq_len), jnp.int32)
    # see gpt_capture: identical param tree, no init-time logits tensor
    params = model.init(rng, dummy, return_hidden=streaming_loss)["params"]

    if streaming_loss:
        def loss_fn(p, batch):
            hidden = model.apply({"params": p}, batch["tokens"],
                                 return_hidden=True)
            t = batch["targets"]
            return streaming_softmax_xent(
                hidden, p["lm_head"], t,
                valid=_positional_mask(t, batch.get(BATCH_MASK_KEY)),
                chunk=loss_chunk, layout="dv")
    else:
        def loss_fn(p, batch):
            logits = model.apply({"params": p}, batch["tokens"])
            return llama_loss(logits, batch["targets"],
                              batch.get(BATCH_MASK_KEY))

    return loss_fn, params, ["embed"]


def _routed_lm_capture(model, seq_len, rng, loss_chunk, tied=False):
    """``(loss_fn, params, sparse_vars)`` of a causal LM whose ``apply``
    returns ``(hidden states, stats)`` with ``stats`` the routed layers'
    counters (``models/qwen3_next.py:routing_counters``), under a head that
    the loss streams (``ops/losses.py``): the untied ``lm_head`` (``[D,
    V]``, "dv"), or with ``tied`` the embedding ``embed`` (``[V, D]``, "vd").

    ``loss_fn(params, batch) -> (loss, counters)``: pass ``has_aux=True`` to
    ``distribute``; the step's metrics then carry ``moe_rows_here``,
    ``moe_load_max_over_mean`` and ``moe_overflow_rows``.  If any layer was
    sent more assignments than its ``rows_bound`` holds, the loss is
    ``inf``: the surplus was not computed, and nobody should train on
    without knowing."""
    from autodist_tpu.models.qwen3_next import routing_counters
    from autodist_tpu.ops.losses import streaming_softmax_xent

    rng = rng if rng is not None else host_key(0)
    dummy = jnp.zeros((1, seq_len), jnp.int32)
    params = model.init(rng, dummy, return_hidden=True)["params"]
    head, layout = ("embed", "vd") if tied else ("lm_head", "dv")

    def loss_fn(p, batch):
        hidden, stats = model.apply({"params": p}, batch["tokens"],
                                    return_hidden=True)
        t = batch["targets"]
        loss = streaming_softmax_xent(
            hidden, p[head], t,
            valid=_positional_mask(t, batch.get(BATCH_MASK_KEY)),
            chunk=loss_chunk, layout=layout)
        counters = routing_counters(jax.lax.stop_gradient(stats))
        return jnp.where(counters["moe_overflow_rows"] > 0, jnp.inf,
                         loss), counters

    return loss_fn, params, []


def qwen3_next_capture(config, seq_len, rng=None, loss_chunk=8192):
    """Init a Qwen3-Next causal LM; returns (loss_fn, params, sparse_vars)
    as ``_routed_lm_capture`` describes them."""
    from autodist_tpu.models.qwen3_next import Qwen3Next

    return _routed_lm_capture(Qwen3Next(config), seq_len, rng, loss_chunk)


def nemotron_h_capture(config, seq_len, rng=None, loss_chunk=8192):
    """Init a Nemotron-H causal LM (``models/nemotron_h.py``); returns
    (loss_fn, params, sparse_vars) as ``_routed_lm_capture`` describes
    them.  The pattern needs a routed layer."""
    from autodist_tpu.models.nemotron_h import NemotronH

    return _routed_lm_capture(NemotronH(config), seq_len, rng, loss_chunk)


def lfm2_capture(config, seq_len, rng=None, loss_chunk=8192):
    """Init an LFM2 causal LM (``models/lfm2.py``); returns (loss_fn, params,
    sparse_vars) as ``_routed_lm_capture`` describes them, the loss streamed
    over the tied embedding.  The layers kept need a routed one."""
    from autodist_tpu.models.lfm2 import Lfm2

    return _routed_lm_capture(Lfm2(config), seq_len, rng, loss_chunk,
                              tied=True)


def lm_capture(config, seq_len, rng=None):
    """The embedding table is a TOP-LEVEL param (not flax-managed) so a
    PartitionedPS strategy can shard it end-to-end: the engine then hands
    the loss a ``ShardedTable`` local block that ``embedding_lookup``
    row-exchanges (flax's own param shape check would reject it)."""
    from autodist_tpu.models.lm import LSTMBody, lm_loss
    from autodist_tpu.ops.sparse import embedding_lookup

    rng = rng if rng is not None else host_key(0)
    c = config
    body = LSTMBody(c)
    k_emb, k_body = jax.random.split(rng)
    emb = jax.random.normal(k_emb, (c.vocab_size, c.embed_dim),
                            jnp.float32) * 0.05
    dummy = jnp.zeros((1, seq_len, c.embed_dim), c.dtype)
    params = {"embedding": emb, "body": body.init(k_body, dummy)["params"]}

    def loss_fn(p, batch):
        x = embedding_lookup(p["embedding"], batch["tokens"]).astype(c.dtype)
        logits = body.apply({"params": p["body"]}, x)
        return lm_loss(logits, batch["targets"], batch.get(BATCH_MASK_KEY))

    return loss_fn, params, ["embedding"]


def ncf_capture(config, rng=None):
    from autodist_tpu.models.ncf import NeuMF, ncf_loss

    rng = rng if rng is not None else host_key(0)
    model = NeuMF(config)
    dummy = jnp.zeros((1,), jnp.int32)
    params = model.init(rng, dummy, dummy)["params"]

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["user"], batch["item"])
        return ncf_loss(logits, batch["label"], batch.get(BATCH_MASK_KEY))

    sparse = [n for n in ("mf_user_embedding", "mf_item_embedding",
                          "mlp_user_embedding", "mlp_item_embedding")]
    return loss_fn, params, sparse


def sgd_momentum(lr=0.1, momentum=0.9):
    return optax.sgd(lr, momentum=momentum)
