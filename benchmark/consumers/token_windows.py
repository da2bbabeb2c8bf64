"""GPT pretraining's reader: random windows of a tokenized corpus, as
nanoGPT's ``train.py::get_batch`` draws them, landed on the device at
sample grain through ``DeviceBlockLoader.windows``, one jitted step a
batch that sums every window's tokens into a device slot ring.

The corpus is ``uint16`` GPT-2 tokens in shards of one block
(:class:`TokenSet`). The sampler draws a shard and a token offset at
which a window of ``window_tokens`` fits, uniformly, ``batch_windows``
a step (:func:`draws`); every pass of it starts from the seed, as a job
restarted from its seed reads the same batches.

Plain reference, independent of ``alluxio_tpu``: the same draws, each
shard rebuilt from the seed, ``shard[t:t + window_tokens]`` exactly as
``get_batch`` slices ``data[i:i + block_size + 1]``. ``check`` holds
every window's token sum of every step to it (a window from another
shard or offset moves its sum: the shard shift changes every token),
and one whole batch byte for byte."""

from __future__ import annotations

import numpy as np

from benchmark.harness.loader_cell import LoaderCell

#: GPT-2's vocabulary: every token is below it
VOCAB = 50257
#: steps the device's slot ring holds; the check reads the last of them
RING_STEPS = 1 << 14


def _rng(seed: int, *stream: int):
    return np.random.default_rng([int(seed), *stream])


class TokenSet:
    """``n_files`` shards of ``file_bytes`` of little-endian ``uint16``
    tokens in [0, ``VOCAB``): a random base shard from the seed, shard
    ``i`` = base + i (mod ``VOCAB``). What ``bdata.ingest`` asks of a
    set: ``n_files``, ``file_bytes``, ``paths``, ``total_bytes``,
    ``file(i, out=)``."""

    def __init__(self, seed: int, n_files: int, file_bytes: int,
                 prefix: str = "/bench/tokens") -> None:
        self.n_files = n_files
        self.file_bytes = file_bytes
        self.tokens = file_bytes // 2
        self.paths = [f"{prefix}-{i:04d}" for i in range(n_files)]
        self._base = _rng(seed, 0).integers(0, VOCAB, size=self.tokens,
                                            dtype=np.uint16)

    @property
    def total_bytes(self) -> int:
        return self.n_files * self.file_bytes

    def _shift(self, base: np.ndarray, i: int, out: np.ndarray):
        """(base + i) mod VOCAB in uint16 with no overflow: base - d
        where base >= d, else base + k (< VOCAB), d = VOCAB - k."""
        k = i % VOCAB
        d = np.uint16(VOCAB - k)
        np.subtract(base, d, out=out)
        np.add(base, np.uint16(k), out=out, where=base < d)
        return out

    def file(self, i: int, out=None) -> np.ndarray:
        if out is None:
            out = np.empty(self.file_bytes, np.uint8)
        self._shift(self._base, i, out.view("<u2"))
        return out

    def windows(self, files, starts, n_tokens: int) -> np.ndarray:
        """``(len(files), n_tokens)`` uint16: shard ``files[j]``'s tokens
        ``starts[j]`` on, each shard rebuilt from the base."""
        base = self._base[np.asarray(starts)[:, None]
                          + np.arange(n_tokens)]
        out = np.empty_like(base)
        for j, i in enumerate(np.asarray(files).tolist()):
            self._shift(base[j], i, out[j])
        return out


def draws(seed: int, n_files: int, n_tokens: int, window_tokens: int,
          batch: int):
    """The sampler, from the seed: ``(files, token starts)`` a batch,
    forever. nanoGPT draws ``randint(len(data) - block_size)``, so a
    window of ``block_size + 1`` tokens ends at the corpus' end at the
    latest; here a shard, then a start in it."""
    rng = _rng(seed, 1)
    while True:
        files = rng.integers(0, n_files, size=batch)
        starts = rng.integers(0, n_tokens - window_tokens + 1, size=batch)
        yield files, starts


def reference_draws(seed: int, n_files: int, n_tokens: int,
                    window_tokens: int, batch: int, n_steps: int):
    """``(files, starts)`` of the first ``n_steps`` batches, stacked."""
    it = draws(seed, n_files, n_tokens, window_tokens, batch)
    got = [next(it) for _ in range(n_steps)]
    if not got:
        return np.zeros((0, batch), np.int64), np.zeros((0, batch), np.int64)
    return (np.stack([f for f, _s in got]), np.stack([s for _f, s in got]))


def make_step(batch: int, window_bytes: int):
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def bench_token_window_sums(windows, ring, k):
        tokens = lax.bitcast_convert_type(
            windows.reshape(batch, window_bytes // 2, 2), jnp.uint16)
        sums = jnp.sum(tokens.astype(jnp.uint32), axis=1)
        return ring.at[k % RING_STEPS].set(sums), k + 1, sums

    return bench_token_window_sums


class Consumer(LoaderCell):
    def __init__(self, **kw) -> None:
        super().__init__(**kw)
        from alluxio_tpu.client.jax_io import DeviceBlockLoader

        if not hasattr(DeviceBlockLoader, "windows"):
            # before the ingest: a program without sample-grain reads
            # cannot run this cell, and says so at once
            raise SystemExit(
                "DeviceBlockLoader has no windows(): this program reads "
                "whole blocks only and cannot run sample-grain windows")
        config, traffic = self.config, self.traffic
        self.dataset = TokenSet(self.seed, traffic["files"],
                                config["block_bytes"])
        if self.dataset.total_bytes != config["set_bytes"]:
            raise SystemExit(
                f"the configuration states a set of {config['set_bytes']} "
                f"bytes; the traffic file makes {self.dataset.total_bytes}")
        self.batch = config["batch_size"] * config["micro_batches"]
        self.window_tokens = config["block_size"] + 1
        if (traffic["batch_windows"], traffic["window_tokens"]) != (
                self.batch, self.window_tokens):
            raise SystemExit(
                f"the traffic's batches of {traffic['batch_windows']} x "
                f"{traffic['window_tokens']} tokens are not the "
                f"configuration's {self.batch} x {self.window_tokens}")
        self.window_bytes = self.window_tokens * config["token_bytes"]
        self.warm_items = traffic["warm_batches"]
        self.n_steps = 0
        self._kept = None  # (step, batch): the first batch of the window

    def sampler(self):
        ds = self.dataset
        for files, starts in draws(self.seed, ds.n_files, ds.tokens,
                                   self.window_tokens, self.batch):
            yield np.stack([files, 2 * starts], axis=1)

    def open(self, fs) -> None:
        import jax
        import jax.numpy as jnp

        super().open(fs)
        self._step = make_step(self.batch, self.window_bytes)
        self._ring = jax.device_put(
            jnp.zeros((RING_STEPS, self.batch), jnp.uint32), self.device)
        self._k = jax.device_put(jnp.int32(0), self.device)

    def items(self, loader):
        return loader.windows(self.sampler(), window_bytes=self.window_bytes)

    def step(self, batch):
        if self.n_steps == self.warm_items:
            self._kept = (self.n_steps, batch)
        self._ring, self._k, token = self._step(batch, self._ring, self._k)
        self.n_steps += 1
        return token, batch.nbytes

    # -- against the plain reference --------------------------------------------
    def check(self) -> dict:
        ds = self.dataset
        n = self.n_steps
        files, starts = reference_draws(self.seed, ds.n_files, ds.tokens,
                                        self.window_tokens, self.batch, n)
        first = max(0, n - RING_STEPS)
        want = np.stack([
            ds.windows(files[s], starts[s], self.window_tokens)
            .astype(np.uint32).sum(axis=1, dtype=np.uint32)
            for s in range(first, n)]) if n else np.zeros((0, self.batch))
        ring = np.asarray(self._ring)
        got = ring[np.arange(first, n) % RING_STEPS]
        bad = np.argwhere(got != want)
        failed = len({int(s) for s, _r in bad})  # steps with a wrong window
        notes = {"steps": n, "windows_checked": int(want.size),
                 "first_mismatches": [[first + int(s), int(r)]
                                      for s, r in bad[:8]]}
        if self._kept is not None:
            s, batch = self._kept
            ref = ds.windows(files[s], starts[s], self.window_tokens)
            same = np.array_equal(np.asarray(batch),
                                  ref.astype("<u2").view(np.uint8))
            notes["batch_bytes_equal"] = [s, same]
            failed += 0 if same else 1
        return {"failed": failed, "notes": notes}
