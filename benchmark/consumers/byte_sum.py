"""One jitted uint32 byte-sum a block: the lightest consumer that still
reads every byte on the device, so the cell's rate is the path's.

Plain reference: the seed's generator rebuilt on the host
(``ByteSet.byte_sum``), per-file sums mod 2**32, independent of the
client. Every step adds its sum into one slot a file of a device
buffer, fetched ONCE after the window: slot ``i`` must hold (times file
``i`` was consumed) x (its reference sum) mod 2**32."""

from __future__ import annotations

import numpy as np

from benchmark.harness.data import ByteSet
from benchmark.harness.loader_cell import LoaderCell


def sum_bytes_needed(block_bytes: int) -> int:
    """Bytes the sum has to move: one read of the block from HBM (the
    uint32 scalar it writes is noise)."""
    return block_bytes


def reference_slots(dataset, n_steps: int) -> np.ndarray:
    """What the slot buffer must hold after ``n_steps`` steps in file
    order, passes back to back."""
    n = dataset.n_files
    counts = np.full(n, n_steps // n, np.uint64)
    counts[:n_steps % n] += 1
    sums = np.array([dataset.byte_sum(i) for i in range(n)], np.uint64)
    return ((counts * sums) & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def make_step(n_files: int):
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(1, 2))
    def bench_sum_bytes(block, slots, k):
        s = jnp.sum(block.astype(jnp.uint32))
        return slots.at[k].add(s), (k + 1) % n_files, s

    return bench_sum_bytes


class Consumer(LoaderCell):
    def __init__(self, **kw) -> None:
        super().__init__(**kw)
        self.dataset = ByteSet(self.seed, self.traffic["files"],
                               self.config["block_bytes"])
        self.warm_items = self.traffic["warm_files"]
        self.step_bytes_needed = sum_bytes_needed(self.dataset.file_bytes)
        self.n_steps = 0

    def open(self, fs) -> None:
        import jax
        import jax.numpy as jnp

        super().open(fs)
        self._step = make_step(self.dataset.n_files)
        self._slots = jax.device_put(
            jnp.zeros(self.dataset.n_files, jnp.uint32), self.device)
        self._k = jax.device_put(jnp.int32(0), self.device)

    def items(self, loader):
        return loader.epoch()

    def step(self, block):
        self._slots, self._k, token = self._step(block, self._slots, self._k)
        self.n_steps += 1
        return token, block.nbytes

    def check(self) -> dict:
        got = np.asarray(self._slots)
        want = reference_slots(self.dataset, self.n_steps)
        bad = np.flatnonzero(got != want)
        return {"failed": int(bad.size),
                "notes": {"steps": self.n_steps,
                          "first_mismatches": bad[:8].tolist()}}
