"""One jitted uint32 byte-sum a block, the blocks read in a seeded
permutation that is drawn anew every epoch: a training job's shuffle,
over a set larger than the HBM tier, through the clairvoyant prefetch
service as a user wires it (``PrefetchService.from_conf`` with
``atpu.prefetch.enabled``, ``.start()``, ``DeviceBlockLoader(...,
prefetch_service=svc)``, ``loader.epoch()`` again and again).

Plain reference, independent of ``alluxio_tpu.prefetch``: the order of
epoch ``e`` is ``default_rng(SeedSequence([seed, e])).permutation(n)``
(:func:`reference_order`, the oracle's documented contract), the bytes
are the seed's generator rebuilt on the host (``ByteSet.byte_sum``).
Every step adds its block's sum into the slot the REFERENCE order names
for that step; the order goes to the device once an epoch as an index
array and the position is a device scalar the step advances, so no host
value crosses a step. The slots are fetched ONCE after the window: slot
``i`` must hold (times the reference order visited file ``i`` in the
steps made) x (file ``i``'s reference sum) mod 2**32, so a block out of
order fails as a wrong byte does."""

from __future__ import annotations

import statistics
import time

import numpy as np

from benchmark.harness import discover
from benchmark.harness.data import ByteSet
from benchmark.harness.loader_cell import LoaderCell

#: the step's kernel is scan-16g's byte sum: its bytes are counted there
sum_bytes_needed = discover.load_module(
    "consumers", "byte_sum").sum_bytes_needed

#: the service's counters, which ``run.py`` does not hand to a reader:
#: snapshotted here when the warm-up ends and when the window closes
PREFETCH_COUNTERS = (
    "Client.PrefetchHits", "Client.PrefetchLate", "Client.PrefetchMisses",
    "Client.PrefetchLateArrivals", "Client.PrefetchHbmAdopted")


def reference_order(seed: int, epoch: int, n: int) -> np.ndarray:
    """Epoch ``epoch``'s access order over ``n`` blocks."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), int(epoch)])).permutation(n)


def reference_slots(dataset, seed: int, n_steps: int) -> np.ndarray:
    """What the slot buffer must hold after ``n_steps`` steps, epochs
    back to back from epoch 0: every whole epoch visits each file once,
    the last one the head of its permutation."""
    n = dataset.n_files
    counts = np.full(n, n_steps // n, np.uint64)
    counts[reference_order(seed, n_steps // n, n)[:n_steps % n]] += 1
    sums = np.array([dataset.byte_sum(i) for i in range(n)], np.uint64)
    return ((counts * sums) & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def make_step(n_files: int):
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(1, 3))
    def bench_sum_bytes_shuffled(block, slots, order, k):
        s = jnp.sum(block.astype(jnp.uint32))
        return slots.at[order[k]].add(s), (k + 1) % n_files, s

    return bench_sum_bytes_shuffled


def prefetch_counters() -> dict:
    from alluxio_tpu.metrics import metrics

    snap = metrics().snapshot()
    return {name: snap.get(name, 0) for name in PREFETCH_COUNTERS}


class Job:
    """A user's job: the service and the loader bound to it, closed
    together (what ``LoaderCell`` asks of a loader)."""

    def __init__(self, svc, loader) -> None:
        self.svc, self.loader = svc, loader

    def epoch(self):
        return self.loader.epoch()

    def hbm_stats(self) -> dict:
        return self.loader.hbm_stats()

    def close(self) -> None:
        self.loader.close()
        self.svc.close()


class Consumer(LoaderCell):
    def __init__(self, **kw) -> None:
        super().__init__(**kw)
        self.dataset = ByteSet(self.seed, self.traffic["files"],
                               self.config["block_bytes"])
        if self.dataset.total_bytes != self.config["set_bytes"]:
            raise SystemExit(
                f"the configuration states a set of "
                f"{self.config['set_bytes']} bytes; the traffic file "
                f"makes {self.dataset.total_bytes}")
        self.warm_items = self.traffic["warm_files"]
        self.step_bytes_needed = sum_bytes_needed(self.dataset.file_bytes)
        self.n_steps = 0
        self.manifest_ms = []      # of every job start, the window's first
        self.prefetch_window = None  # whole-window counter deltas
        self._at_warm = None
        self._svc_stats = None

    # -- the job ---------------------------------------------------------------
    def new_loader(self, fs, paths=None, *, hbm_bytes=None):
        """Service from conf (the manifest's build), the loader bound
        to it, the service started: a job's start, so
        ``client.loader_ctor_ms`` is manifest + constructor."""
        from alluxio_tpu.client.jax_io import DeviceBlockLoader
        from alluxio_tpu.conf import Configuration, Keys
        from alluxio_tpu.prefetch import PrefetchService

        conf = Configuration(load_env=False)
        for key, value in self.config["service"].items():
            conf.set(key, value)
        depth = self.config["prefetch"]
        self._depth = depth if depth is not None else conf.get_int(
            Keys.TPU_PREFETCH_BUFFER_BATCHES)
        paths = self.dataset.paths if paths is None else paths
        t0 = time.perf_counter()
        svc = PrefetchService.from_conf(conf, fs, paths, seed=self.seed)
        self.manifest_ms.append((time.perf_counter() - t0) * 1e3)
        loader = DeviceBlockLoader(
            fs, paths, device=self.device, prefetch=self.config["prefetch"],
            hbm_bytes=self.config["hbm_bytes"] if hbm_bytes is None
            else hbm_bytes, prefetch_service=svc)
        svc.start()
        return Job(svc, loader)

    def open(self, fs) -> None:
        import jax
        import jax.numpy as jnp

        super().open(fs)
        self._jax = jax
        self.setup_items = {"manifest_ms": self.manifest_ms[0]}
        self._step = make_step(self.dataset.n_files)
        self._slots = jax.device_put(
            jnp.zeros(self.dataset.n_files, jnp.uint32), self.device)
        self._k = jax.device_put(jnp.int32(0), self.device)
        self._order = None

    def items(self, loader):
        return loader.epoch()

    def step(self, block):
        n = self.dataset.n_files
        if self.n_steps % n == 0:  # once an epoch: its reference order
            self._order = self._jax.device_put(
                reference_order(self.seed, self.n_steps // n, n)
                .astype(np.int32), self.device)
        self._slots, self._k, token = self._step(
            block, self._slots, self._order, self._k)
        self.n_steps += 1
        if self.n_steps == self.warm_items:  # the window starts here
            self._at_warm = prefetch_counters()
        return token, block.nbytes

    def close_window(self) -> dict:
        now = prefetch_counters()
        if self._at_warm is not None:
            self.prefetch_window = {
                name: now[name] - self._at_warm[name] for name in now}
        self._svc_stats = self.loader.svc.stats()
        return super().close_window()

    # -- against the plain reference --------------------------------------------
    def check(self) -> dict:
        got = np.asarray(self._slots)
        want = reference_slots(self.dataset, self.seed, self.n_steps)
        bad = np.flatnonzero(got != want)
        failed = int(bad.size)
        notes = {"steps": self.n_steps,
                 "first_mismatches": bad[:8].tolist(),
                 "prefetch_window": self.prefetch_window,
                 # the manifest's part of loader_ctor_ms in a cold start
                 "cold_manifest_ms": statistics.median(self.manifest_ms[1:])
                 if self.manifest_ms[1:] else None}
        # every block the producer consumed was classified once: the
        # window's service (epoch 0 on) saw the steps made and at most
        # the loader's depth more (its queue, the block in the
        # producer's hand, the transfers it keeps in flight)
        st = self._svc_stats
        if st is not None:
            consumed = st["hits"] + st["late"] + st["misses"]
            depth = 2 * max(1, self._depth) + 3
            notes["service"] = {k: st[k] for k in (
                "hits", "late", "misses", "late_arrivals", "epoch", "pos")}
            if not self.n_steps <= consumed <= self.n_steps + depth:
                failed += 1
                notes["consumed_vs_steps"] = [consumed, self.n_steps]
        return {"failed": failed, "notes": notes}
