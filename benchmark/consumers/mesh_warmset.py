"""The four-chip warm set as a job uses it: 512 x 32 MiB sharded over
the chips' HBM by ``MeshBlockCache.load_global`` (4 GiB a chip,
placement reported to the master), each step assembling ``batch`` blocks
by GLOBAL index with the program's ``batch_fn`` fused into the jitted
per-row byte sum, and after the window a few cold JOB STARTS: a new
client, a new loader over the whole list, a new cache, ``load_global``
of the whole set, the first step on the new array.

It takes ``mesh_batch_sum``'s step, index table and loop (an import, not
an edit) and adds the job start, the program's own split of the set-up's
``load_global`` and the bytes a step has to move.

Plain reference: the seed's generator on the host (``ByteSet``). Held
to it: every step's row sums (one fetch of the slot table after the
window), every loaded row's sum, one assembled batch byte for byte,
every cold start's first batch; besides, the real sharding, the master's
placement record of every warm set while it lives and after its drop,
and the lowering free of all-gather.

The jobs of this cell follow one another on the host: the window's job
drops its placement when its window ends (its array stays for the checks
alone), so no two records are alive at once here. Two warm sets alive at
once on one host are held apart by the program's tier-1 tests
(``tests/test_mesh_warmset.py``), not by this cell: the parent commit's
master keeps one record a (block, position) and cannot."""

from __future__ import annotations

import time

import numpy as np

from benchmark.harness import discover

_base = discover.load_module("consumers", "mesh_batch_sum")
TABLE_ROWS = _base.TABLE_ROWS
index_table = _base.index_table

COUNTER_PREFIX = "Client.JaxMesh"


def hbm_bytes_needed(batch: int, n_owners: int, block_bytes: int) -> int:
    """HBM bytes EACH chip has to move a step: its own ``batch /
    n_owners`` rows read out of its shard, the (batch, block_bytes)
    assembly written once and read once by the consumer."""
    return (batch // n_owners + batch + batch) * block_bytes


def ici_bytes_needed(batch: int, n_owners: int, block_bytes: int) -> int:
    """ICI bytes EACH chip has to move a step so that every chip holds
    the whole batch: 2 x (n - 1) / n of the assembly. That is what a
    ring all-reduce of it sends from a chip, and also the least a chip
    sends plus receives however the rows travel (the other owners' rows
    in, its own rows out to each of them), which is how the peak of
    ``peaks.json`` (a chip's whole ICI bandwidth) is read here."""
    return 2 * (n_owners - 1) * batch * block_bytes // n_owners


def mesh_counters() -> dict:
    from alluxio_tpu.metrics import metrics

    return {k: v for k, v in metrics().snapshot().items()
            if k.startswith(COUNTER_PREFIX)}


class Consumer(_base.Consumer):
    def __init__(self, **kw) -> None:
        super().__init__(**kw)
        n = len(self.devices)
        self.step_floor_bytes = {
            "hbm": hbm_bytes_needed(self.batch, n, self.dataset.file_bytes),
            "ici": ici_bytes_needed(self.batch, n, self.dataset.file_bytes)}
        self._placement_wrong = 0
        self._cold_rows_wrong = 0
        self._cold_starts = 0

    # -- the window's warm set ------------------------------------------------
    def open(self, fs) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec

        from alluxio_tpu.parallel.ici_store import MeshBlockCache
        from alluxio_tpu.parallel.mesh import make_mesh

        ds = self.dataset
        self.fs = fs
        self.mesh = make_mesh(devices=self.devices)
        self.cache = MeshBlockCache(self.mesh, block_bytes=ds.file_bytes)
        before = mesh_counters()
        t0 = time.perf_counter()
        self.cached = self.cache.load_global(fs, ds.paths)
        jax.block_until_ready(self.cached)
        self.setup_items["load_global_s"] = time.perf_counter() - t0
        after = mesh_counters()
        # the program's own split of that load (a program without the
        # counters: nothing); host_read is summed over the pool's threads
        for item, counter in (("mesh_host_read_s", "HostReadUs"),
                              ("mesh_stack_s", "StackUs"),
                              ("mesh_put_s", "PutUs")):
            name = COUNTER_PREFIX + counter
            self.setup_items[item] = \
                (after[name] - before.get(name, 0)) * 1e-6 \
                if name in after else None

        n_dev = len(self.devices)
        self.per_dev = self.cached.shape[0] // n_dev
        self.table = index_table(self.seed, ds.n_files, n_dev, self.batch)
        self._rep = NamedSharding(self.mesh, PartitionSpec())
        assemble = self.cache.batch_fn(self.per_dev)

        def bench_mesh_batch_sum(cached, table, slots, t):
            rows = assemble(cached, table[t])
            sums = jnp.sum(rows.astype(jnp.uint32), axis=1)
            return slots.at[t].add(sums), (t + 1) % TABLE_ROWS, sums

        self._table = jax.device_put(self.table, self._rep)
        self._slots, self._t = self._fresh_state()
        t0 = time.perf_counter()
        self._step = jax.jit(bench_mesh_batch_sum, donate_argnums=(2, 3)) \
            .lower(self.cached, self._table, self._slots, self._t).compile()
        self.setup_items["step_compile_s"] = time.perf_counter() - t0
        self._hlo = self._step.as_text()
        self._sums = np.array([ds.byte_sum(i) for i in range(ds.n_files)],
                              np.uint64)

    def _fresh_state(self):
        import jax
        import jax.numpy as jnp

        return (jax.device_put(
            jnp.zeros((TABLE_ROWS, self.batch), jnp.uint32), self._rep),
            jax.device_put(jnp.int32(0), self._rep))

    def close_window(self) -> dict:
        # the window's job is over: its record is read while it lives,
        # then dropped, as a job that ends drops it
        self._read_and_drop_record(self.fs, self.cache)
        return {}

    def _read_and_drop_record(self, fs, cache) -> None:
        self._placement_wrong += self._record_wrong(fs, cache)
        cache.drop_placement(fs)
        self._placement_wrong += self._record_wrong(fs, cache, dropped=True)

    def _record_wrong(self, fs, cache, dropped: bool = False) -> int:
        """Owners whose first block the master does not place at that
        owner of ``cache`` (or, after the drop, places anywhere)."""
        wrong = 0
        for pos in range(len(self.devices)):
            info = fs.store.block_master.get_block_info(
                cache.block_ids[pos * self.per_dev])
            where = [(loc.address.host,
                      loc.address.tiered_identity.tiers[-1].value)
                     for loc in info.device_locations]
            want = [] if dropped else [(cache.client_host, str(pos))]
            wrong += where != want
        return wrong

    # -- a job's cold start ----------------------------------------------------
    def cold_start(self) -> dict:
        """A job start as a user makes it, timed from before a new
        ``FileSystem`` to the first batch's sums being ready: new
        client, new ``DeviceBlockLoader`` over the WHOLE list (timed
        alone), new ``MeshBlockCache``, ``load_global`` of the whole
        set, the first step of the compiled program on the new array.
        Then, outside the timing: that batch's row sums against the
        reference, a few ``get_status`` by the benchmark's clock, the new
        set's placement record read and dropped, its array deleted."""
        import jax

        from alluxio_tpu.client.jax_io import DeviceBlockLoader
        from alluxio_tpu.parallel.ici_store import MeshBlockCache

        ds = self.dataset
        slots, t = self._fresh_state()
        jax.block_until_ready((slots, t))
        t0 = time.perf_counter()
        fs = self.roles.file_system()
        t1 = time.perf_counter()
        loader = DeviceBlockLoader(fs, ds.paths, hbm_bytes=0)
        t2 = time.perf_counter()
        try:
            cache = MeshBlockCache(self.mesh, block_bytes=ds.file_bytes)
            cached = cache.load_global(fs, ds.paths, loader=loader)
            _slots, _t, sums = self._step(cached, self._table, slots, t)
            sums.block_until_ready()
            t3 = time.perf_counter()
            want = (self._sums[self.table[0]]
                    & np.uint64(0xFFFFFFFF)).astype(np.uint32)
            self._cold_rows_wrong += int((np.asarray(sums) != want).sum())
            status_ms = []
            for path in ds.paths[:16]:
                s0 = time.perf_counter()
                fs.get_status(path)
                status_ms.append((time.perf_counter() - s0) * 1e3)
            self._read_and_drop_record(fs, cache)
            cached.delete()
        finally:
            loader.close()
            fs.close()
        self._cold_starts += 1
        return {"first_batch_ms": (t3 - t0) * 1e3,
                "loader_ctor_ms": (t2 - t1) * 1e3,
                "get_status_ms": status_ms}

    # -- checks against the plain reference ------------------------------------
    def check(self) -> dict:
        import jax
        import jax.numpy as jnp

        ds, n_dev = self.dataset, len(self.devices)
        low32 = np.uint64(0xFFFFFFFF)
        notes = {"steps": self.n_steps, "cold_starts": self._cold_starts}
        # every step's row sums, one fetch
        counts = np.full(TABLE_ROWS, self.n_steps // TABLE_ROWS, np.uint64)
        counts[:self.n_steps % TABLE_ROWS] += 1
        want = ((counts[:, None] * self._sums[self.table]) & low32) \
            .astype(np.uint32)
        notes["row_sums_wrong"] = int((np.asarray(self._slots) != want).sum())
        # every loaded row against the host's data, one fetch; the rows
        # past the last block are zero
        row_sums = np.asarray(jax.jit(
            lambda x: jnp.sum(x.astype(jnp.uint32), axis=1))(self.cached))
        notes["shards_wrong"] = int(
            (row_sums[:ds.n_files]
             != (self._sums & low32).astype(np.uint32)).sum()
            + (row_sums[ds.n_files:] != 0).sum())
        # one assembled batch, byte for byte
        got = np.asarray(self.cache.global_batch(self.cached, self.table[0]))
        scratch = np.empty(ds.file_bytes, np.uint8)
        notes["batch_rows_wrong"] = sum(
            not np.array_equal(got[r], ds.file(int(g), out=scratch))
            for r, g in enumerate(self.table[0]))
        del got
        notes["cold_rows_wrong"] = self._cold_rows_wrong
        # placement: the real sharding; every warm set's record at the
        # master while it lived and after its drop (close_window,
        # cold_start); nothing left behind now
        owners = self.cache.describe_placement(self.cached)
        sharded = sorted(owners) == list(range(n_dev)) and all(
            rows == list(range(pos * self.per_dev, (pos + 1) * self.per_dev))
            for pos, rows in owners.items())
        notes["placement_wrong"] = self._placement_wrong + (not sharded) \
            + self._record_wrong(self.fs, self.cache, dropped=True)
        gather_free = "all-gather" not in self._hlo and \
            (n_dev == 1 or "all-reduce" in self._hlo)
        notes["all_gather_free"] = gather_free
        failed = sum(notes[k] for k in (
            "row_sums_wrong", "shards_wrong", "batch_rows_wrong",
            "cold_rows_wrong", "placement_wrong")) + (not gather_free)
        return {"failed": int(failed), "notes": notes}
