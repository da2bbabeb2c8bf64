"""Record shards -> ``batched_device_iterator`` -> the program's
``decode_image_records`` -> one jitted linear-softmax SGD step
(``bench._bench_e2e``'s shape: f32 12,288 x 1,000). The step is light on
purpose: it asks for far more than the path delivers, so the cell's
rate reads the path.

Plain reference (NumPy, f32, no jit): the records rebuilt from the seed;
the labels of sampled steps must equal the records written, the first
step's loss must equal a NumPy step on the same batch, every loss must
be finite."""

from __future__ import annotations

import numpy as np

from benchmark.harness.data import (IMAGE_SHAPE, LABEL_BYTES, N_CLASSES,
                                    RecordSet)
from benchmark.harness.loader_cell import LoaderCell

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)
LR = 1e-3
#: |loss - reference|: the device multiplies f32 in bf16 passes by
#: default and decode hands bf16 pixels on, so logits of std ~1.3 carry
#: ~1e-2 of rounding; a wrong record or label moves the loss by ~1
LOSS_TOL = 5e-2


def init_params(seed: int, feat: int) -> dict:
    rng = np.random.default_rng([int(seed), 7])
    return {"w": (rng.standard_normal((feat, N_CLASSES)) * 0.01
                  ).astype(np.float32),
            "b": np.zeros(N_CLASSES, np.float32)}


def reference_loss(records: np.ndarray, params: dict) -> float:
    """Decode + loss of one batch in plain NumPy f32 (pixels rounded to
    bf16 as the decode's contract says)."""
    import ml_dtypes

    lab = records[:, :LABEL_BYTES].copy().view("<i4").reshape(-1)
    h, w, c = IMAGE_SHAPE
    x = records[:, LABEL_BYTES:].reshape(-1, h, w, c).astype(np.float32)
    x = (x / np.float32(255.0) - np.asarray(MEAN, np.float32)) \
        / np.asarray(STD, np.float32)
    x = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    logits = x.reshape(x.shape[0], -1) @ params["w"] + params["b"]
    logits -= logits.max(axis=1, keepdims=True)
    logp = logits - np.log(np.exp(logits).sum(axis=1, keepdims=True))
    return float(-logp[np.arange(lab.size), lab].mean())


def make_step():
    import functools

    import jax
    import jax.numpy as jnp

    from alluxio_tpu.ops.decode import decode_image_records

    h, w, c = IMAGE_SHAPE

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def bench_train_step(params, rec_batch, nonfinite):
        imgs, lab = decode_image_records(rec_batch, height=h, width=w,
                                         channels=c)

        def loss_fn(p):
            x = imgs.reshape(imgs.shape[0], -1).astype(jnp.float32)
            logits = x @ p["w"] + p["b"]
            return -jnp.mean(jax.nn.log_softmax(logits)[
                jnp.arange(lab.shape[0]), lab])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params = jax.tree_util.tree_map(lambda p, g: p - LR * g,
                                        params, grads)
        return (params, nonfinite + (~jnp.isfinite(loss)).astype(jnp.int32),
                loss, lab)

    return bench_train_step


class Consumer(LoaderCell):
    def __init__(self, **kw) -> None:
        super().__init__(**kw)
        self.batch = self.traffic["batch"]
        self.dataset = RecordSet(self.seed, self.traffic["files"],
                                 self.config["block_bytes"])
        ds = self.dataset
        self.batches_per_pass = ds.n_files * ds.per_file // self.batch
        self.warm_items = self.traffic["warm_files"] * ds.per_file \
            // self.batch
        self.sample_every = self.traffic["check_every"]
        self.n_steps = 0
        self._samples = []  # [(step, loss, labels)] device handles

    def open(self, fs) -> None:
        import jax
        import jax.numpy as jnp

        super().open(fs)
        self._host_params = init_params(
            self.seed, self.dataset.record_bytes - LABEL_BYTES)
        self._params = jax.device_put(self._host_params, self.device)
        self._nonfinite = jax.device_put(jnp.int32(0), self.device)
        self._step = make_step()

    def items(self, loader):
        from alluxio_tpu.client.jax_io import batched_device_iterator

        return batched_device_iterator(
            loader, record_bytes=self.dataset.record_bytes,
            batch_size=self.batch)

    def step(self, rec_batch):
        self._params, self._nonfinite, loss, lab = self._step(
            self._params, rec_batch, self._nonfinite)
        if self.n_steps % self.sample_every == 0:
            self._samples.append((self.n_steps, loss, lab))
        self.n_steps += 1
        return loss, rec_batch.nbytes

    def check(self) -> dict:
        import jax

        ds = self.dataset
        nonfinite, fetched = jax.device_get(
            (self._nonfinite, [(l, lab) for _s, l, lab in self._samples]))
        failed = int(nonfinite)
        bad_labels = []
        for (step, _l, _lab), (_loss, lab) in zip(self._samples, fetched):
            first = (step % self.batches_per_pass) * self.batch
            if not np.array_equal(lab, ds.stream_labels(first, self.batch)):
                bad_labels.append(step)
        failed += len(bad_labels)
        want = reference_loss(ds.records(0)[:self.batch], self._host_params)
        got = float(fetched[0][0])
        if not abs(got - want) <= LOSS_TOL:
            failed += 1
        return {"failed": failed,
                "notes": {"steps": self.n_steps, "nonfinite": int(nonfinite),
                          "label_batches_checked": len(fetched),
                          "label_batches_wrong": bad_labels[:8],
                          "first_loss": got, "first_loss_reference": want,
                          "last_sampled_loss": float(fetched[-1][0])}}
