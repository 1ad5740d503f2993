"""ResNet family: image classification on seeded uint8 records read through
the loader; the cast to the compute type and the normalisation happen on the
device, in this file's wrapper of the loss function, as a user's input
function would do them.
"""
import os

import numpy as np

UNIT = "images"
# ImageNet's channel statistics on the 0..255 scale, the usual normalisation.
MEAN = (123.675, 116.28, 103.53)
STD = (58.395, 57.12, 57.375)


def build_model(config):
    import jax.numpy as jnp

    from autodist_tpu.models import resnet

    a = config["assumed"]
    if config["depth"] != 50:
        raise ValueError("this family builds the 50-layer bottleneck net")
    return resnet.ResNet(
        stage_sizes=list(config["stage_sizes"]),
        block_cls=resnet.BottleneckResNetBlock,
        num_classes=config["num_classes"], num_filters=config["num_filters"],
        dtype=jnp.dtype(a["compute_dtype"]).type, norm=a["norm"])


def write_image_records(path, n_records, image_size, num_classes, seed):
    """Records of ``image_size**2 * 3`` uint8 pixels followed by an int32
    label (little-endian, as four bytes).  Labels are Zipf-distributed over
    the classes and each image is seeded noise plus a seeded 7x7 pattern of
    its class, so both the label frequencies and the classes can be learnt
    and a falling loss means the update was applied."""
    from autodist_tpu.data.loader import write_records

    r = np.random.RandomState(seed)
    p = 1.0 / np.arange(1, num_classes + 1)
    labels = r.choice(num_classes, size=n_records, p=p / p.sum()).astype("<i4")
    cells = 7
    if image_size % cells:
        raise ValueError(f"image_size must be a multiple of {cells}")
    patterns = r.randint(0, 256, (num_classes, cells, cells, 3), np.uint8)
    up = image_size // cells
    recs = np.empty((n_records, image_size * image_size * 3 + 4), np.uint8)
    for lo in range(0, n_records, 128):
        lab = labels[lo:lo + 128]
        noise = r.randint(0, 256, (len(lab), image_size, image_size, 3),
                          np.uint8)
        pat = patterns[lab].repeat(up, axis=1).repeat(up, axis=2)
        recs[lo:lo + 128, :-4] = (noise // 2 + pat // 2).reshape(len(lab), -1)
    recs[:, -4:] = labels.view(np.uint8).reshape(n_records, 4)
    write_records(path, recs)


class ImageStream:
    """The records read back through RecordDataset -> BatchLoader and split
    into uint8 images and int32 labels."""

    def __init__(self, path, image_size, batch, seed, feed):
        from autodist_tpu.data.loader import BatchLoader, RecordDataset

        self._shape = (image_size, image_size, 3)
        self._ds = RecordDataset(path, (int(np.prod(self._shape)) + 4,),
                                 np.uint8)
        self._loader = BatchLoader(
            self._ds, batch, seed=seed, threads=feed["loader_threads"],
            prefetch=feed["loader_prefetch"])

    def __iter__(self):
        return self

    def __next__(self):
        recs = next(self._loader)
        n = recs.shape[0]
        return {"image": recs[:, :-4].reshape((n,) + self._shape),
                "label": np.ascontiguousarray(recs[:, -4:]).view("<i4")
                .reshape(n)}

    def close(self):
        self._loader.close()
        self._ds.close()


class Job:
    """One cell's training job, as the harness drives it."""

    unit = UNIT

    def __init__(self, cell, config, seed, work_dir):
        from autodist_tpu.models import train_lib

        a = config["assumed"]
        self.cell, self.config, self.seed = cell, config, seed
        self.image_size = config["image_size"]
        self.units_per_step = cell["batch"]
        self.model = build_model(config)
        self.optimizer = train_lib.sgd_momentum(a["learning_rate"],
                                                a["momentum"])
        self.distribute_kwargs = {}
        self.loss_fn = None
        path = os.path.join(work_dir, "images.bin")
        write_image_records(path, cell["feed"]["records"], self.image_size,
                            config["num_classes"], seed)
        self.stream = ImageStream(path, self.image_size, cell["batch"], seed,
                                  cell["feed"])

    def make_params(self):
        """The seeded weights and batch statistics, made on the device in
        one jitted call."""
        import jax
        import jax.numpy as jnp

        from autodist_tpu.models import train_lib
        from autodist_tpu.utils.rng import host_key

        dtype = jnp.dtype(self.config["assumed"]["compute_dtype"])
        mean = np.asarray(MEAN, np.float32)
        inv_std = 1.0 / np.asarray(STD, np.float32)

        def init(key):
            raw_loss, params, state = train_lib.classifier_capture(
                self.model, (self.image_size, self.image_size, 3), rng=key)

            def loss_fn(p, s, batch):
                image = (batch["image"].astype(jnp.float32) - mean) * inv_std
                return raw_loss(p, s, {**batch, "image": image.astype(dtype)})

            self.loss_fn = loss_fn
            return params, state

        params, state = jax.jit(init)(host_key(self.seed))
        self.distribute_kwargs["mutable_state"] = state
        return params

    def flops_per_unit(self, params):
        from benchmark.harness.flops import resnet50_train_flops_per_image

        if self.image_size != 224 or self.config["stage_sizes"] != [3, 4, 6, 3]:
            return None     # the count is ResNet-50's at 224x224, no other's
        return resnet50_train_flops_per_image()

    def reference_losses(self, params, batches, device):
        """Losses of the plain step with batch statistics on ``batches``
        from ``params``: one ``jax.jit`` of ``value_and_grad`` + the
        same optimizer on ``device``, no engine."""
        import jax
        import optax

        p = jax.device_put(params, device)
        bn = jax.device_put(self.distribute_kwargs["mutable_state"], device)
        loss_fn, optimizer = self.loss_fn, self.optimizer

        @jax.jit
        def step(p, s, bn, b):
            (loss, bn), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                p, bn, b)
            updates, s = optimizer.update(grads, s, p)
            return optax.apply_updates(p, updates), s, bn, loss

        s = jax.jit(optimizer.init)(p)     # a jit output, as the later calls' is
        losses = []
        for b in batches:
            p, s, bn, loss = step(p, s, bn, jax.device_put(b, device))
            losses.append(float(loss))
        return losses

    def close(self):
        self.stream.close()


def layer_shapes(cell, config):
    return {"batch_per_chip": cell["batch"] // cell["chips"]}
