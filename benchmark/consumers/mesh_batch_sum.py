"""The ICI data plane as a consumer uses it: the set sharded over every
chip's HBM by ``MeshBlockCache.load_global`` (placement reported to the
master), each step assembling ``batch`` blocks by global index with the
program's ``batch_fn`` FUSED into the jitted per-row byte sum.

Plain reference: the seed's generator on the host. Per-row sums land in
a device buffer fetched ONCE after the window (slot ``[t, r]`` = times
table row ``t`` ran x reference sum of block ``table[t, r]``, mod
2**32); then, once each, every loaded shard's sum against the host's,
one assembled batch byte for byte, the master's record of one block per
owner, and the lowering free of all-gather."""

from __future__ import annotations

import os
import time

import numpy as np

from benchmark.harness.data import ByteSet

#: distinct index batches a run cycles through
TABLE_ROWS = 64


def sum_bytes_needed(batch: int, block_bytes: int) -> int:
    """Bytes the per-row sum has to move on EACH chip: one read of the
    replicated (batch, block_bytes) assembly."""
    return batch * block_bytes


def index_table(seed: int, n_blocks: int, n_owners: int, batch: int):
    """``TABLE_ROWS`` batches of global block indices; every batch takes
    ``batch / n_owners`` blocks from each owner's shard (so every seed
    moves the same bytes over the same links, in another order)."""
    rng = np.random.default_rng([int(seed), 11])
    per_dev = -(-n_blocks // n_owners)
    each = batch // n_owners
    rows = []
    for _ in range(TABLE_ROWS):
        idx = np.concatenate([
            pos * per_dev + rng.choice(
                min(per_dev, n_blocks - pos * per_dev), each, replace=False)
            for pos in range(n_owners)])
        rows.append(rng.permutation(idx))
    return np.asarray(rows, np.int32)


class Consumer:
    def __init__(self, *, config, traffic, seed, devices, roles) -> None:
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices = list(devices)
        self.roles = roles
        self.batch = traffic["batch"]
        if self.batch % len(self.devices):
            raise SystemExit(f"batch {self.batch} does not spread over "
                             f"{len(self.devices)} owners")
        self.dataset = ByteSet(seed, traffic["files"], config["block_bytes"])
        self.warm_items = traffic["warm_steps"]
        self.step_bytes_needed = sum_bytes_needed(
            self.batch, self.dataset.file_bytes)
        self.n_steps = 0
        self.setup_items = {}

    def open(self, fs) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from alluxio_tpu.parallel.ici_store import MeshBlockCache
        from alluxio_tpu.parallel.mesh import make_mesh

        ds = self.dataset
        self.fs = fs
        mesh = make_mesh(devices=self.devices)
        self.cache = MeshBlockCache(
            mesh, block_bytes=ds.file_bytes,
            client_host=f"bench-{os.getpid()}")
        t0 = time.perf_counter()
        self.cached = self.cache.load_global(fs, ds.paths)
        jax.block_until_ready(self.cached)
        self.setup_items["load_global_s"] = time.perf_counter() - t0

        n_dev = len(self.devices)
        self.per_dev = self.cached.shape[0] // n_dev
        self.table = index_table(self.seed, ds.n_files, n_dev, self.batch)
        rep = NamedSharding(mesh, PartitionSpec())
        assemble = self.cache.batch_fn(self.per_dev)

        def bench_mesh_batch_sum(cached, table, slots, t):
            rows = assemble(cached, table[t])
            sums = jnp.sum(rows.astype(jnp.uint32), axis=1)
            return slots.at[t].add(sums), (t + 1) % TABLE_ROWS, sums

        table = jax.device_put(self.table, rep)
        self._table = table
        self._slots = jax.device_put(
            jnp.zeros((TABLE_ROWS, self.batch), jnp.uint32), rep)
        self._t = jax.device_put(jnp.int32(0), rep)
        t0 = time.perf_counter()
        self._step = jax.jit(bench_mesh_batch_sum, donate_argnums=(2, 3)) \
            .lower(self.cached, table, self._slots, self._t).compile()
        self.setup_items["step_compile_s"] = time.perf_counter() - t0
        self._hlo = self._step.as_text()

    def inputs(self):
        while True:
            yield None  # indices live on the device: nothing to wait for

    def step(self, _item):
        self._slots, self._t, token = self._step(
            self.cached, self._table, self._slots, self._t)
        self.n_steps += 1
        return token, self.batch * self.dataset.file_bytes

    def close_window(self) -> dict:
        return {}

    def check(self) -> dict:
        import jax
        import jax.numpy as jnp

        ds, n_dev = self.dataset, len(self.devices)
        sums = np.array([ds.byte_sum(i) for i in range(ds.n_files)],
                        np.uint64)
        notes = {"steps": self.n_steps}
        failed = 0
        # every step's row sums, one fetch
        counts = np.full(TABLE_ROWS, self.n_steps // TABLE_ROWS, np.uint64)
        counts[:self.n_steps % TABLE_ROWS] += 1
        want = ((counts[:, None] * sums[self.table])
                & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        wrong = int((np.asarray(self._slots) != want).sum())
        notes["row_sums_wrong"] = wrong
        failed += wrong
        # every loaded shard against the host's data, one fetch
        shard_sums = np.asarray(jax.jit(
            lambda x: jnp.sum(x.astype(jnp.uint32), axis=1))(self.cached))
        wrong = int((shard_sums[:ds.n_files]
                     != (sums & np.uint64(0xFFFFFFFF)).astype(np.uint32)).sum())
        notes["shards_wrong"] = wrong
        failed += wrong
        # one assembled batch, byte for byte
        got = np.asarray(self.cache.global_batch(self.cached, self.table[0]))
        scratch = np.empty(ds.file_bytes, np.uint8)
        wrong = sum(not np.array_equal(got[r], ds.file(int(g), out=scratch))
                    for r, g in enumerate(self.table[0]))
        notes["batch_rows_wrong"] = wrong
        failed += wrong
        del got
        # placement: the real sharding, and what the master recorded
        owners = self.cache.describe_placement(self.cached)
        ok = sorted(owners) == list(range(n_dev)) and \
            all(len(v) == self.per_dev for v in owners.values())
        for pos in range(n_dev):
            bid = self.cache.block_ids[pos * self.per_dev]
            info = self.fs.store.block_master.get_block_info(bid)
            where = [(loc.address.host,
                      loc.address.tiered_identity.tiers[-1].value)
                     for loc in info.device_locations]
            ok = ok and where == [(self.cache.client_host, str(pos))]
        notes["placement_ok"] = ok
        failed += 0 if ok else 1
        gather_free = "all-gather" not in self._hlo and \
            (n_dev == 1 or "all-reduce" in self._hlo)
        notes["all_gather_free"] = gather_free
        failed += 0 if gather_free else 1
        self.cache.drop_placement(self.fs)
        return {"failed": failed, "notes": notes}
