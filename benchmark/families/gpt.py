"""GPT-2 family: causal LM on a seeded Zipf corpus read through the loader.

The configuration file carries openai/gpt-2's ``hparams.json`` keys
(``n_vocab``, ``n_ctx``, ``n_embd``, ``n_head``, ``n_layer``) and, under
``assumed``, what the hparams leave to the code (the 4x MLP) and the
training set-up.  Copied from ``chip_smoke.py``: the corpus, the stream that
remembers its batches, the plain reference.
"""
import dataclasses
import os

import numpy as np

UNIT = "tokens"


def gpt_config(config):
    import jax.numpy as jnp

    from autodist_tpu.models.gpt import GPTConfig

    a = config["assumed"]
    return GPTConfig(
        vocab_size=config["n_vocab"], hidden_size=config["n_embd"],
        num_layers=config["n_layer"], num_heads=config["n_head"],
        intermediate_size=a["mlp_ratio"] * config["n_embd"],
        max_position=config["n_ctx"], dropout_rate=0.0,
        dtype=jnp.dtype(a["compute_dtype"]).type,
        attention_impl=a["attention_impl"], remat=a["remat"])


def write_token_corpus(path, n_records, seq_len, vocab_size, seed):
    """A seeded corpus with Zipf token frequencies.  Uniform tokens cannot be
    learnt; here the unigram distribution can, so a falling loss means the
    update was applied."""
    from autodist_tpu.data.loader import write_records

    r = np.random.RandomState(seed)
    p = 1.0 / np.arange(1, vocab_size + 1)
    toks = r.choice(vocab_size, size=(n_records, seq_len + 1), p=p / p.sum())
    write_records(path, toks.astype(np.int32))


class TokenStream:
    """The corpus read back through RecordDataset -> BatchLoader."""

    def __init__(self, path, seq_len, batch, seed, feed):
        from autodist_tpu.data.loader import BatchLoader, RecordDataset

        self._ds = RecordDataset(path, (seq_len + 1,), np.int32)
        self._loader = BatchLoader(
            self._ds, batch, seed=seed, threads=feed["loader_threads"],
            prefetch=feed["loader_prefetch"])

    def __iter__(self):
        return self

    def __next__(self):
        recs = next(self._loader)
        return {"tokens": recs[:, :-1], "targets": recs[:, 1:]}

    def close(self):
        self._loader.close()
        self._ds.close()


class Job:
    """One cell's training job, as the harness drives it."""

    unit = UNIT

    def __init__(self, cell, config, seed, work_dir):
        import optax

        self.cell, self.seed = cell, seed
        self.cfg = gpt_config(config)
        self.seq_len = cell["seq_len"]
        self.units_per_step = cell["batch"] * self.seq_len
        self.optimizer = optax.adamw(config["assumed"]["learning_rate"])
        self.distribute_kwargs = {"has_rng": True}
        corpus = os.path.join(work_dir, "corpus.bin")
        write_token_corpus(corpus, cell["feed"]["records"], self.seq_len,
                           self.cfg.vocab_size, seed)
        self.stream = TokenStream(corpus, self.seq_len, cell["batch"], seed,
                                  cell["feed"])
        self.loss_fn = None
        self.sparse_vars = None

    def make_params(self):
        """The seeded weights, made on the device in one jitted call."""
        import jax

        from autodist_tpu.models.train_lib import gpt_capture
        from autodist_tpu.utils.rng import host_key

        def init(key):
            loss_fn, params, sparse = gpt_capture(
                self.cfg, self.seq_len, rng=key, streaming_loss=True)
            self.loss_fn, self.sparse_vars = loss_fn, sparse
            return params

        params = jax.jit(init)(host_key(self.seed))
        self.distribute_kwargs["sparse_vars"] = self.sparse_vars
        return params

    def flops_per_unit(self, params):
        """Model FLOPs per token from the real parameter tree."""
        import jax

        from benchmark.harness.flops import gpt_train_flops_per_token

        n_matmul = sum(
            int(np.prod(leaf.shape))
            for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]
            if "wpe" not in jax.tree_util.keystr(path))
        return gpt_train_flops_per_token(
            n_matmul, self.cfg.num_layers, self.seq_len, self.cfg.hidden_size)

    def reference_losses(self, params, batches, device):
        """Losses of a plain train step on ``batches`` from a copy of
        ``params``: one ``jax.jit`` of ``value_and_grad`` + optax on ``device``,
        XLA attention, no engine.  The batch is taken in
        ``reference.micro_batches`` equal parts whose gradients are averaged
        (every position counts, so the mean of the parts' mean losses is the
        batch's), which keeps this phase's memory under the engine's.  With
        ``reference.train`` false (a model one chip cannot train) only the
        forward loss is taken."""
        import jax
        import jax.numpy as jnp
        import optax

        from autodist_tpu.models.train_lib import gpt_capture
        from autodist_tpu.utils.rng import host_key

        ref = self.cell["reference"]
        micro, train = ref["micro_batches"], ref.get("train", True)
        loss_fn, _, _ = gpt_capture(
            dataclasses.replace(self.cfg, attention_impl="xla"),
            self.seq_len, streaming_loss=True)
        rng = host_key(0)   # dropout is off: the key only fills the signature
        optimizer = self.optimizer

        def split(b):
            return jax.tree.map(
                lambda x: x.reshape((micro, -1) + x.shape[1:]), b)

        def forward(p, b):
            return jnp.mean(jax.lax.map(lambda one: loss_fn(p, one, rng),
                                        split(b)))

        def step(p, s, b):
            def body(acc, one):
                out = jax.value_and_grad(loss_fn)(p, one, rng)
                return jax.tree.map(jnp.add, acc, out), None

            zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
            (loss, grads), _ = jax.lax.scan(body, zero, split(b))
            grads = jax.tree.map(lambda g: g / micro, grads)
            updates, s = optimizer.update(grads, s, p)
            return optax.apply_updates(p, updates), s, loss / micro

        def start(p):       # a copy to donate: the caller keeps its weights
            p = jax.tree.map(jnp.copy, p)
            return p, optimizer.init(p)

        p = jax.device_put(params, device)
        if not train:
            jforward = jax.jit(forward)
            return [float(jforward(p, jax.device_put(b, device)))
                    for b in batches]
        # every argument of every call is a jit output or a device_put, so
        # the second call finds the first's executable: a second variant is
        # a second minute of compiling and a second 70 MB cache entry
        jstep = jax.jit(step, donate_argnums=(0, 1))
        p, s = jax.jit(start)(p)
        losses = []
        for b in batches:
            p, s, loss = jstep(p, s, jax.device_put(b, device))
            losses.append(float(loss))
        return losses

    def close(self):
        self.stream.close()


def layer_shapes(cell, config):
    """What the per-layer readers need of the model's shapes."""
    return {"batch_per_chip": cell["batch"] // cell["chips"],
            "heads": config["n_head"], "seq_len": cell["seq_len"],
            "head_dim": config["n_embd"] // config["n_head"],
            "layers": config["n_layer"]}
