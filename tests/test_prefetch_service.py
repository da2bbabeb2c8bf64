"""Clairvoyant prefetch subsystem tests: oracle determinism, scheduler
budget/lateness accounting, eviction-pin survival, and the end-to-end
oracle -> scheduler -> agent loop against the minicluster (the ISSUE's
acceptance run: a seeded two-epoch pass with >=90% resident reads)."""

import time

import numpy as np
import pytest

from alluxio_tpu.minicluster import LocalCluster
from alluxio_tpu.prefetch import (
    AccessOracle, BlockRef, DatasetManifest, PrefetchScheduler,
    PrefetchService, TIER_DRAM, TIER_HBM,
)

BLOCK = 64 * 1024


def make_manifest(n=10, length=10):
    return DatasetManifest(blocks=tuple(
        BlockRef(path="/data", block_index=i, block_id=100 + i,
                 length=length) for i in range(n)))


class TestOracle:
    def test_fixed_seed_is_deterministic(self):
        m = make_manifest()
        a = AccessOracle(m, seed=7)
        b = AccessOracle(m, seed=7)
        for epoch in (0, 1, 5):
            assert [r.block_id for r in a.epoch_sequence(epoch)] == \
                [r.block_id for r in b.epoch_sequence(epoch)]

    def test_epochs_and_seeds_differ(self):
        m = make_manifest(32)
        o = AccessOracle(m, seed=7)
        e0 = [r.block_id for r in o.epoch_sequence(0)]
        e1 = [r.block_id for r in o.epoch_sequence(1)]
        assert sorted(e0) == sorted(e1)  # same corpus
        assert e0 != e1                  # reshuffled
        assert e0 != [r.block_id
                      for r in AccessOracle(m, seed=8).epoch_sequence(0)]

    def test_host_shards_partition_the_epoch(self):
        m = make_manifest(11)
        shards = [AccessOracle(m, seed=3, num_hosts=3, host_index=h)
                  for h in range(3)]
        seen = [r.block_id for o in shards for r in o.epoch_sequence(0)]
        assert sorted(seen) == sorted(b.block_id for b in m.blocks)
        assert sum(o.epoch_len() for o in shards) == 11

    def test_window_crosses_epoch_boundary(self):
        m = make_manifest(4)
        o = AccessOracle(m, seed=1)
        win = o.window(0, 2, 5)  # 2 left in epoch 0 + 3 from epoch 1
        assert [seq for seq, _ in win] == [2, 3, 4, 5, 6]
        assert [r.block_id for _, r in win[2:]] == \
            [r.block_id for r in o.epoch_sequence(1)[:3]]


    @pytest.mark.parametrize("num_hosts,host_index",
                             [(1, 0), (2, 0), (2, 1)])
    def test_next_use_is_a_search_of_the_sequences(self, num_hosts,
                                                   host_index):
        """At or after (epoch, pos): the rest of this epoch, then the
        next ones, a strided shard's skipped epochs included; the
        inverse is built when asked for, not with the oracle."""
        from alluxio_tpu.prefetch import oracle as oracle_mod

        m = make_manifest(11)
        o = AccessOracle(m, seed=5, num_hosts=num_hosts,
                         host_index=host_index)
        assert o._positions == {}
        per_epoch = o.epoch_len()
        horizon = oracle_mod._EPOCH_CACHE_SIZE

        def search(block_id, epoch, pos):
            for e in range(epoch, epoch + horizon):
                ids = [r.block_id for r in o.epoch_sequence(e)]
                for at in range(pos if e == epoch else 0, len(ids)):
                    if ids[at] == block_id:
                        return e * per_epoch + at
            return oracle_mod.NEVER

        skipped = 0
        for epoch in (0, 3):
            for pos in (0, 1, per_epoch // 2, per_epoch - 1, per_epoch):
                for b in m.blocks:
                    want = search(b.block_id, epoch, pos)
                    assert o.next_use(b.block_id, epoch, pos) == want
                    skipped += want >= (epoch + 2) * per_epoch
        # one host reads every block every epoch; a shard of two skips
        assert (skipped > 0) == (num_hosts > 1)
        assert o.next_use(999, 0, 0) == oracle_mod.NEVER  # no such block
        assert set(o._positions) <= set(o._cache)

    def test_the_service_answers_against_the_cursor(self):
        """``served`` steps over the access at the cursor (a hit is
        looked up before its consume moves the cursor)."""
        o = AccessOracle(make_manifest(6), seed=9)
        s = PrefetchScheduler(o, lookahead_blocks=6, budget_bytes=0)
        svc = PrefetchService(o, s, agent=None)
        seq = [r.block_id for r in o.epoch_sequence(0)]
        nxt = [r.block_id for r in o.epoch_sequence(1)]
        for _ in range(2):
            s.on_consume(o.epoch_sequence(0)[s.cursor()[1]])
        at = seq[2]  # the block at the cursor
        assert svc.next_use(at) == 2
        assert svc.next_use(at, served=True) == 6 + nxt.index(at)
        assert svc.next_use(seq[0]) == 6 + nxt.index(seq[0])  # consumed
        assert svc.next_use(seq[5]) == svc.next_use(seq[5], True) == 5


class TestScheduler:
    def _sched(self, n=10, length=10, **kw):
        o = AccessOracle(make_manifest(n, length), seed=7)
        kw.setdefault("lookahead_blocks", n)
        kw.setdefault("budget_bytes", n * length)
        kw.setdefault("hbm_fraction", 0.0)
        return o, PrefetchScheduler(o, **kw)

    def test_budget_never_exceeded(self):
        o, s = self._sched(budget_bytes=35)
        rng = np.random.default_rng(0)
        held_max = 0
        for _ in range(200):
            for a in s.plan():
                s.on_loaded(a.ref.block_id)
            held = s.held_bytes(TIER_DRAM) + s.held_bytes(TIER_HBM)
            held_max = max(held_max, held)
            assert held <= 35
            # consume the next access (hit or miss, budget must hold)
            epoch, pos = s.cursor()
            s.on_consume(o.epoch_sequence(epoch)[pos])
            if rng.random() < 0.3:  # jitter: replan mid-stream
                s.plan()
        assert held_max > 0  # the invariant was actually exercised

    def test_hbm_fraction_splits_the_budget(self):
        _, s = self._sched(budget_bytes=100, hbm_fraction=0.3)
        actions = s.plan()
        hbm = [a for a in actions if a.tier == TIER_HBM]
        dram = [a for a in actions if a.tier == TIER_DRAM]
        assert sum(a.ref.length for a in hbm) <= 30
        assert sum(a.ref.length for a in dram) <= 70
        assert hbm and dram

    def test_deadlines_are_consume_order(self):
        _, s = self._sched()
        actions = s.plan()
        assert [a.deadline_seq for a in actions] == \
            list(range(len(actions)))

    def test_hit_late_miss_accounting(self):
        o, s = self._sched(n=4, lookahead_blocks=2, budget_bytes=20)
        seq = o.epoch_sequence(0)
        actions = s.plan()  # plans accesses 0 and 1
        assert len(actions) == 2
        s.on_loaded(actions[0].ref.block_id)
        base = s.stats()
        assert s.on_consume(seq[0]) == "hit"      # ready before consume
        assert s.on_consume(seq[1]) == "late"     # issued, never landed
        assert s.on_consume(seq[2]) == "miss"     # never planned
        stats = s.stats()
        assert stats["hits"] - base["hits"] == 1
        assert stats["late"] - base["late"] == 1
        assert stats["misses"] - base["misses"] == 1
        # the straggler lands after its deadline passed: visible, not a hit
        s.on_loaded(actions[1].ref.block_id)
        assert s.stats()["late_arrivals"] >= base["late_arrivals"] + 1

    def test_backpressure_stops_at_nearest_deadline(self):
        _, s = self._sched(budget_bytes=25)  # room for 2 of 10-byte blocks
        actions = s.plan()
        assert [a.deadline_seq for a in actions] == [0, 1]
        assert s.plan() == []  # saturated: no further placements
        s.on_loaded(actions[0].ref.block_id)
        assert s.plan() == []  # ready bytes still count against budget
        s.on_consume(actions[0].ref)  # hit: frees 10 bytes
        assert len(s.plan()) == 1     # exactly the freed headroom

    def test_failed_load_releases_budget(self):
        _, s = self._sched(budget_bytes=25, retry_backoff_s=0.0)
        actions = s.plan()
        for a in actions:
            s.on_load_failed(a.ref.block_id)
        assert s.held_bytes(TIER_DRAM) == 0
        assert len(s.plan()) == 2  # replanned (no backoff configured)

    def test_failed_load_backs_off_before_replan(self):
        """A permanently-failing placement (HBM store too small, dead
        worker) must not become a replan-every-tick hot loop."""
        _, s = self._sched(budget_bytes=25, retry_backoff_s=60.0)
        failed = [a.ref.block_id for a in s.plan()]
        for bid in failed:
            s.on_load_failed(bid)
        assert s.held_bytes(TIER_DRAM) == 0  # budget released
        # cooling-down blocks are skipped; the freed budget goes to the
        # NEXT deadlines instead of hot-looping on the failures
        replanned = [a.ref.block_id for a in s.plan()]
        assert replanned and not set(replanned) & set(failed)

    def test_stale_generation_consume_is_fenced(self):
        """A superseded epoch's producer slipping one last consume past
        a begin_epoch must not advance the new epoch's cursor."""
        o, s = self._sched()
        gen0 = s.begin_epoch(0)
        gen1 = s.begin_epoch(0)  # consumer restarted the epoch
        seq = o.epoch_sequence(0)
        assert s.on_consume(seq[0], generation=gen0) == "stale"
        assert s.cursor() == (0, 0)  # fenced: cursor untouched
        assert s.on_consume(seq[0], generation=gen1) == "miss"
        assert s.cursor() == (0, 1)

    def test_invalidate_drops_ready_state(self):
        """Out-of-band residency loss (worker free/remove) must turn the
        next consume into a replan, not a phantom hit."""
        o, s = self._sched(budget_bytes=100)
        actions = s.plan()
        s.on_loaded(actions[0].ref.block_id)
        assert s.is_ready(actions[0].ref.block_id)
        s.on_evicted(actions[0].ref.block_id)
        assert not s.is_ready(actions[0].ref.block_id)
        assert s.held_bytes(TIER_DRAM) == \
            sum(a.ref.length for a in actions[1:])
        assert s.on_consume(o.epoch_sequence(0)[0]) != "hit"


class TestExecutorTimeout:
    def test_unpinnable_pending_block_fails_out(self):
        """A placement whose pin can never be taken (stale master
        location for a restarted worker) must time out and release its
        budget instead of holding it forever."""
        from alluxio_tpu.prefetch.agent import WorkerTierExecutor

        class _Addr:
            pass

        class _Info:
            def __init__(self, locs):
                self.locations = locs

        class _BM:
            resident = False

            def get_block_info(self, bid):
                loc = type("L", (), {"address": _Addr()})()
                info = _Info([loc] if self.resident else [])
                info.block_id = bid
                return info

            def get_block_infos(self, bids):
                return [self.get_block_info(b) for b in bids]

            def get_worker_infos(self):
                return [type("W", (), {"address": _Addr()})()]

        class _WC:
            def async_cache(self, *a, **k):
                return True

            def prefetch_pin(self, bid):
                return False  # worker lost the block

        bm = _BM()
        ex = WorkerTierExecutor(bm, lambda addr: _WC(),
                                load_timeout_s=0.0)
        ref = BlockRef(path="/f", block_index=0, block_id=1, length=10,
                       ufs_path="/u/f", persisted=True)
        assert ex.submit(ref)
        bm.resident = True  # committed, but the pin keeps failing
        done, failed = ex.poll()
        assert done == [] and failed == [1]
        assert not ex.pinned_blocks()


class TestEvictionPins:
    def _store(self, tmp_path, cap):
        from alluxio_tpu.worker.allocator import Allocator
        from alluxio_tpu.worker.annotator import BlockAnnotator
        from alluxio_tpu.worker.meta import BlockMetadataManager
        from alluxio_tpu.worker.tiered_store import TieredBlockStore

        meta = BlockMetadataManager()
        meta.add_tier("MEM").add_dir(str(tmp_path / "mem0"), cap)
        return TieredBlockStore(meta, Allocator.create("MAX_FREE", meta),
                                BlockAnnotator.create("LRU"))

    def _put(self, store, bid, nbytes):
        store.create_block(1, bid, initial_bytes=nbytes)
        with store.get_temp_writer(1, bid) as w:
            w.append(b"x" * nbytes)
        return store.commit_block(1, bid)

    def test_prefetch_pinned_blocks_survive_eviction_pressure(self, tmp_path):
        store = self._store(tmp_path, cap=4096)
        self._put(store, 1, 1024)
        assert store.pin_prefetch(1)
        # pressure: fill the tier several times over; the LRU-coldest
        # block (1) is exactly the eviction candidate the pin must veto
        for bid in range(2, 10):
            self._put(store, bid, 1024)
        assert store.has_block(1)
        assert not store.pin_prefetch(999)  # absent block: not pinnable
        store.unpin_prefetch(1)
        for bid in range(10, 14):
            self._put(store, bid, 1024)
        assert not store.has_block(1)  # unpinned: evictable again

    def test_expired_pin_is_reclaimed(self, tmp_path):
        """TTL backstop: a client that died without unpinning must not
        leave blocks unevictable forever."""
        store = self._store(tmp_path, cap=4096)
        self._put(store, 1, 1024)
        assert store.pin_prefetch(1, ttl_s=0.0)  # expires immediately
        for bid in range(2, 10):
            self._put(store, bid, 1024)
        assert not store.has_block(1)  # expired pin did not veto
        assert 1 not in store.prefetch_pinned_blocks

    def test_remove_block_drops_the_pin(self, tmp_path):
        store = self._store(tmp_path, cap=4096)
        self._put(store, 1, 64)
        store.pin_prefetch(1)
        store.remove_block(1)
        assert 1 not in store.prefetch_pinned_blocks


def _write_cold_corpus(cluster, fs, n_files, file_bytes, base="/prefetch"):
    """Cold-start precondition, via the benches' shared recipe."""
    from alluxio_tpu.stress.cluster import write_cold_corpus

    rng = np.random.default_rng(0)
    corpus = {f"{base}/f-{i:03d}": rng.integers(
        0, 255, size=file_bytes, dtype=np.uint8).tobytes()
        for i in range(n_files)}
    write_cold_corpus(fs, cluster.block_client(), corpus)
    return list(corpus)


@pytest.fixture()
def hb_cluster(tmp_path):
    from alluxio_tpu.conf import Keys

    with LocalCluster(
            str(tmp_path), num_workers=1, block_size=BLOCK,
            start_worker_heartbeats=True,
            conf_overrides={
                Keys.WORKER_BLOCK_HEARTBEAT_INTERVAL: "50ms",
                Keys.MASTER_WORKER_TIMEOUT: "10000min",
            }) as c:
        yield c


def _make_service(cluster, fs, paths, *, hbm_fraction=0.0, seed=42):
    from alluxio_tpu.conf import Keys

    conf = cluster.conf.copy()
    conf.set(Keys.PREFETCH_ENABLED, True)
    conf.set(Keys.PREFETCH_LOOKAHEAD_BLOCKS, 64)
    conf.set(Keys.PREFETCH_BUDGET_BYTES, 64 << 20)
    conf.set(Keys.PREFETCH_HBM_FRACTION, hbm_fraction)
    return PrefetchService.from_conf(conf, fs, paths, seed=seed)


def _tick_until_ready(svc, n, timeout_s=30.0):
    assert svc.wait_ready(n, timeout_s=timeout_s, tick=True), \
        f"never reached {n} ready placements: {svc.stats()}"


class TestEndToEnd:
    def test_two_epoch_run_hits_resident_tiers(self, hb_cluster):
        """The acceptance run: seeded two-epoch pass, >=90% of reads
        served from an already-resident (and pinned) tier."""
        from alluxio_tpu.client.jax_io import DeviceBlockLoader

        fs = hb_cluster.file_system()
        paths = _write_cold_corpus(hb_cluster, fs, n_files=2,
                                   file_bytes=4 * BLOCK)
        svc = _make_service(hb_cluster, fs, paths)
        loader = DeviceBlockLoader(fs, paths, prefetch_service=svc)
        total = len(loader)
        base = svc.stats()
        try:
            expected = {}
            for epoch in (0, 1):
                _tick_until_ready(svc, total)
                order = [r.block_id
                         for r in svc.oracle.epoch_sequence(epoch)]
                out = [np.asarray(b).tobytes() for b in loader.epoch()]
                # the consume order IS the oracle's seeded permutation
                if epoch == 0:
                    for bid, data in zip(order, out):
                        expected[bid] = data
                else:
                    assert [expected[bid] for bid in order] == out
            stats = svc.stats()
            consumed = (stats["hits"] - base["hits"]) + \
                (stats["late"] - base["late"]) + \
                (stats["misses"] - base["misses"])
            assert consumed == 2 * total
            hit_rate = (stats["hits"] - base["hits"]) / consumed
            assert hit_rate >= 0.9, f"hit rate {hit_rate}: {stats}"
        finally:
            loader.close()
            svc.close()

    def test_hbm_placements_serve_from_device(self, hb_cluster):
        """hbm.fraction=1: the agent adopts every placement into the
        loader's HBM store; consumes are device-resident hits."""
        from alluxio_tpu.client.jax_io import DeviceBlockLoader
        from alluxio_tpu.metrics import metrics

        fs = hb_cluster.file_system()
        paths = _write_cold_corpus(hb_cluster, fs, n_files=1,
                                   file_bytes=4 * BLOCK, base="/pf-hbm")
        svc = _make_service(hb_cluster, fs, paths, hbm_fraction=1.0)
        loader = DeviceBlockLoader(fs, paths, hbm_bytes=16 << 20,
                                   prefetch_service=svc)
        hbm_hits0 = metrics().counter("Client.JaxHbmHits").count
        base = svc.stats()
        try:
            _tick_until_ready(svc, len(loader))
            assert loader.hbm_stats()["hbm_pages"] == len(loader)
            list(loader.epoch())
            stats = svc.stats()
            assert stats["hits"] - base["hits"] == len(loader)
            assert metrics().counter("Client.JaxHbmHits").count - \
                hbm_hits0 >= len(loader)
        finally:
            loader.close()
            svc.close()

    def test_metrics_surface_in_registry(self, hb_cluster):
        from alluxio_tpu.client.jax_io import DeviceBlockLoader
        from alluxio_tpu.metrics import metrics

        fs = hb_cluster.file_system()
        paths = _write_cold_corpus(hb_cluster, fs, n_files=1,
                                   file_bytes=2 * BLOCK, base="/pf-m")
        svc = _make_service(hb_cluster, fs, paths)
        loader = DeviceBlockLoader(fs, paths, prefetch_service=svc)
        try:
            _tick_until_ready(svc, len(loader))
            list(loader.epoch())
        finally:
            loader.close()
            svc.close()
        snap = metrics().snapshot()
        for name in ("Client.PrefetchHits", "Client.PrefetchLate",
                     "Client.PrefetchMisses",
                     "Client.PrefetchLoadsIssued",
                     "Client.PrefetchBlocksPinned",
                     "Client.PrefetchBlockReady.p99"):
            assert name in snap, name

    def test_disabled_service_resolves_to_none(self, hb_cluster):
        """prefetch.enabled=false -> from_conf yields None, and a loader
        without a service runs the static file-order plan (the pre-
        subsystem behavior, bit for bit)."""
        from alluxio_tpu.client.jax_io import DeviceBlockLoader

        fs = hb_cluster.file_system()
        data = bytes(range(256)) * (2 * BLOCK // 256)
        fs.write_all("/pf-off/data.bin", data)
        assert PrefetchService.from_conf(
            hb_cluster.conf, fs, ["/pf-off/data.bin"], seed=1) is None
        loader = DeviceBlockLoader(fs, ["/pf-off/data.bin"])
        try:
            out = b"".join(np.asarray(b).tobytes()
                           for b in loader.epoch())
            assert out == data  # sequential file order, no reshuffle
        finally:
            loader.close()

    def test_job_service_executor_places_via_load_plans(self, tmp_path):
        """job_client wiring: DRAM placements ride DistributedLoad
        plans (job/plans/load.py) instead of direct worker RPCs, with
        identical readiness/pinning accounting."""
        from alluxio_tpu.client.jax_io import DeviceBlockLoader
        from alluxio_tpu.conf import Keys
        from alluxio_tpu.metrics import metrics

        with LocalCluster(
                str(tmp_path), num_workers=1, block_size=BLOCK,
                start_worker_heartbeats=True, start_job_service=True,
                conf_overrides={
                    Keys.WORKER_BLOCK_HEARTBEAT_INTERVAL: "50ms",
                    Keys.MASTER_WORKER_TIMEOUT: "10000min",
                }) as cluster:
            fs = cluster.file_system()
            paths = _write_cold_corpus(cluster, fs, n_files=2,
                                       file_bytes=2 * BLOCK,
                                       base="/pf-job")
            conf = cluster.conf.copy()
            conf.set(Keys.PREFETCH_ENABLED, True)
            conf.set(Keys.PREFETCH_LOOKAHEAD_BLOCKS, 64)
            conf.set(Keys.PREFETCH_BUDGET_BYTES, 64 << 20)
            conf.set(Keys.PREFETCH_HBM_FRACTION, 0.0)
            jobs0 = metrics().counter("Client.PrefetchLoadJobs").count
            svc = PrefetchService.from_conf(
                conf, fs, paths, seed=5, job_client=cluster.job_client())
            loader = DeviceBlockLoader(fs, paths, prefetch_service=svc)
            base = svc.stats()
            try:
                _tick_until_ready(svc, len(loader))
                list(loader.epoch())
                stats = svc.stats()
                assert stats["hits"] - base["hits"] == len(loader)
                assert metrics().counter(
                    "Client.PrefetchLoadJobs").count > jobs0
            finally:
                loader.close()
                svc.close()

    def test_heartbeat_thread_drives_the_agent(self, hb_cluster):
        """Production wiring: the service's own heartbeat thread (no
        explicit ticks) converges the placements."""
        fs = hb_cluster.file_system()
        paths = _write_cold_corpus(hb_cluster, fs, n_files=1,
                                   file_bytes=2 * BLOCK, base="/pf-hb")
        from alluxio_tpu.conf import Keys

        conf = hb_cluster.conf.copy()
        conf.set(Keys.PREFETCH_ENABLED, True)
        conf.set(Keys.PREFETCH_HEARTBEAT_INTERVAL, "20ms")
        svc = PrefetchService.from_conf(conf, fs, paths, seed=7)
        with svc:
            svc.start()
            assert svc.wait_ready(2, timeout_s=30.0)
