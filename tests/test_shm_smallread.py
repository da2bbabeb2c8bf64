"""Same-host zero-copy plane + scatter/gather batch reads.

Covers the contracts in docs/small_reads.md: lease grant/renew/release
and TTL reclamation (client-crash safety), eviction-vs-mapped exclusion
(under the always-on lock auditor), scatter/gather reassembly over real
gRPC (property sweep), byte-identity of the disabled path, the
minicluster same-host e2e, and the chaos fallbacks behind
``atpu.debug.fault.shm.*``.
"""

import random
import threading
import time

import pytest

from alluxio_tpu.conf import Keys
from alluxio_tpu.metrics import metrics
from alluxio_tpu.minicluster import LocalCluster
from alluxio_tpu.shm import ShmLeaseDeniedError, ShmSegmentUnavailableError
from alluxio_tpu.utils import faults
from alluxio_tpu.utils.exceptions import WorkerOutOfSpaceError
from alluxio_tpu.worker.allocator import Allocator
from alluxio_tpu.worker.annotator import BlockAnnotator
from alluxio_tpu.worker.meta import BlockMetadataManager
from alluxio_tpu.worker.shm_store import ShmStore
from alluxio_tpu.worker.tiered_store import TieredBlockStore

KB = 1024
BLOCK = 64 * KB
SESSION = 11


def make_store(tmp_path, *, mem_cap=10 * KB, ssd_cap=100 * KB):
    meta = BlockMetadataManager()
    mem = meta.add_tier("MEM")
    mem.add_dir(str(tmp_path / "mem0"), mem_cap)
    if ssd_cap:
        ssd = meta.add_tier("SSD")
        ssd.add_dir(str(tmp_path / "ssd0"), ssd_cap)
    return TieredBlockStore(meta, Allocator.create("MAX_FREE", meta),
                            BlockAnnotator.create("LRU"))


def put_block(store, block_id, data, tier="MEM"):
    store.create_block(SESSION, block_id, initial_bytes=len(data),
                       tier_alias=tier)
    with store.get_temp_writer(SESSION, block_id) as w:
        w.append(data)
    return store.commit_block(SESSION, block_id)


# ---------------------------------------------------------------- leases
class TestShmStoreLeases:
    def test_grant_returns_mappable_segment(self, tmp_path):
        store = make_store(tmp_path)
        put_block(store, 1, b"shm-bytes")
        shm = ShmStore(store, lease_ttl_s=30.0)
        lease = shm.open(SESSION, 1)
        assert lease["length"] == 9 and lease["ttl_s"] == 30.0
        with open(lease["path"], "rb") as f:
            assert f.read() == b"shm-bytes"
        assert shm.stats()["live_leases"] == 1
        assert 1 in store.shm_leased_blocks

    def test_every_local_tier_is_mappable(self, tmp_path):
        """A lower tier's file is an ordinary path that mmap takes: the
        lease is granted whatever tier holds the block. Only a block
        this worker does not hold sends the client to the remote rung."""
        store = make_store(tmp_path)
        put_block(store, 2, b"on-ssd", tier="SSD")
        shm = ShmStore(store)
        lease = shm.open(SESSION, 2)
        assert lease["path"] == store.get_block_meta(2).path
        assert store.get_block_meta(2).tier_alias == "SSD"
        with open(lease["path"], "rb") as f:
            assert f.read() == b"on-ssd"
        assert 2 in store.shm_leased_blocks
        with pytest.raises(ShmSegmentUnavailableError):
            shm.open(SESSION, 999)  # not cached at all

    def test_lease_table_full_denies(self, tmp_path):
        store = make_store(tmp_path)
        put_block(store, 1, b"a")
        put_block(store, 2, b"b")
        shm = ShmStore(store, max_leases=1)
        shm.open(SESSION, 1)
        with pytest.raises(ShmLeaseDeniedError):
            shm.open(SESSION, 2)

    def test_renew_extends_release_drops(self, tmp_path):
        store = make_store(tmp_path)
        put_block(store, 1, b"x")
        shm = ShmStore(store, lease_ttl_s=30.0)
        lid = shm.open(SESSION, 1)["lease_id"]
        assert shm.renew(SESSION, lid)["ok"]
        # wrong session must not renew someone else's lease
        assert not shm.renew(SESSION + 1, lid)["ok"]
        assert shm.release(SESSION, lid)
        assert not shm.renew(SESSION, lid)["ok"]
        assert 1 not in store.shm_leased_blocks  # pin lifted eagerly

    def test_close_session_releases_everything(self, tmp_path):
        store = make_store(tmp_path)
        put_block(store, 1, b"a")
        put_block(store, 2, b"b")
        shm = ShmStore(store)
        shm.open(SESSION, 1)
        shm.open(SESSION, 2)
        keep = shm.open(SESSION + 1, 1)  # another session's lease stays
        shm.close_session(SESSION)
        assert shm.stats() == {"live_leases": 1, "leased_blocks": 1,
                               "sessions": 1, "max_leases": 1024,
                               "lease_ttl_s": 30.0}
        assert shm.lease_of(keep["lease_id"]) is not None
        assert 1 in store.shm_leased_blocks  # block 1 still leased

    def test_crashed_client_reclaimed_by_ttl(self, tmp_path):
        """A client that dies without releasing: the lease (and its
        eviction pin) must self-expire — nothing leaks forever."""
        store = make_store(tmp_path)
        put_block(store, 1, b"x")
        shm = ShmStore(store, lease_ttl_s=1.0)
        shm.open(SESSION, 1)
        assert shm.reap_expired() == 0  # not yet
        time.sleep(1.1)
        assert shm.reap_expired() == 1
        assert shm.stats()["live_leases"] == 0
        assert 1 not in store.shm_leased_blocks


# ------------------------------------------------------------- eviction
class TestEvictionVsMapped:
    def test_leased_blocks_skip_eviction(self, tmp_path):
        """A mapped segment must never be unlinked under a reader: the
        shm pin excludes it from eviction; unleased blocks still go."""
        store = make_store(tmp_path, mem_cap=2 * KB, ssd_cap=0)
        put_block(store, 1, b"a" * KB)
        put_block(store, 2, b"b" * KB)
        shm = ShmStore(store, lease_ttl_s=30.0)
        shm.open(SESSION, 1)
        put_block(store, 3, b"c" * KB)  # must evict 2, never leased 1
        report = store.block_report()["MEM"]
        assert 1 in report and 3 in report and 2 not in report

    def test_all_leased_means_out_of_space(self, tmp_path):
        store = make_store(tmp_path, mem_cap=2 * KB, ssd_cap=0)
        put_block(store, 1, b"a" * KB)
        put_block(store, 2, b"b" * KB)
        shm = ShmStore(store)
        shm.open(SESSION, 1)
        shm.open(SESSION, 2)
        with pytest.raises(WorkerOutOfSpaceError):
            put_block(store, 3, b"c" * KB)

    def test_expired_lease_is_evictable(self, tmp_path):
        """TTL expiry lifts the shield without any RPC: a crashed
        client's segment becomes an ordinary eviction candidate."""
        store = make_store(tmp_path, mem_cap=2 * KB, ssd_cap=0)
        put_block(store, 1, b"a" * KB)
        put_block(store, 2, b"b" * KB)
        shm = ShmStore(store, lease_ttl_s=1.0)
        shm.open(SESSION, 1)
        shm.open(SESSION, 2)
        time.sleep(1.1)
        put_block(store, 3, b"c" * KB)  # expired pins reclaimed inline
        assert 3 in store.block_report()["MEM"]

    def test_concurrent_grants_and_eviction_pressure(self, tmp_path):
        """Grants racing allocation pressure: the lock auditor (always
        on in tests) fails this on any registry/alloc lock inversion."""
        store = make_store(tmp_path, mem_cap=4 * KB, ssd_cap=0)
        for i in range(4):
            put_block(store, i, bytes([i]) * KB)
        shm = ShmStore(store, lease_ttl_s=5.0)
        errors = []

        def leaser(bid):
            for _ in range(20):
                try:
                    lease = shm.open(SESSION, bid)
                    shm.release(SESSION, lease["lease_id"])
                except (ShmLeaseDeniedError,
                        ShmSegmentUnavailableError):
                    pass
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        def writer():
            for n in range(10):
                try:
                    put_block(store, 100 + n, b"w" * KB)
                except WorkerOutOfSpaceError:
                    pass
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

        threads = [threading.Thread(target=leaser, args=(i,))
                   for i in range(4)] + [threading.Thread(target=writer)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors


# ----------------------------------------------------- minicluster e2e
@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    base = str(tmp_path_factory.mktemp("shm-cluster"))
    with LocalCluster(base, num_workers=1, block_size=BLOCK,
                      worker_mem_bytes=4 * 1024 * KB) as c:
        yield c


@pytest.fixture(scope="module")
def fs(cluster):
    f = cluster.file_system()
    yield f
    f.close()


def _patterned(n, seed):
    rng = random.Random(seed)
    return bytes(rng.randrange(256) for _ in range(n))


class TestSameHostE2E:
    def test_reads_ride_the_shm_plane(self, fs):
        data = _patterned(BLOCK, 0xE2E)
        fs.write_all("/shm-e2e", data, write_type="MUST_CACHE")
        before = metrics().counter("Client.ShmReads").count
        with fs.open_file("/shm-e2e") as f:
            bs = f.block_stream(0)
            assert bs.pread(0, 512) == data[:512]
            assert bs.last_source == "SHM"
            assert bs.source_bucket() == "shm"
            # the zero-copy views alias one mapping
            v1 = bs.pread_view(0, 512)
            v2 = bs.pread_view(1024, 512)
            assert bytes(v2) == data[1024:1536]
            assert v1.obj is v2.obj
            nv = bs.numpy_view()
            assert nv.nbytes == BLOCK and bytes(nv[:512]) == data[:512]
            del v1, v2, nv
        assert metrics().counter("Client.ShmReads").count > before

    def test_segment_cache_hits_across_opens(self, fs):
        fs.write_all("/shm-cached", _patterned(KB, 1),
                     write_type="MUST_CACHE")
        with fs.open_file("/shm-cached") as f:
            f.block_stream(0).pread(0, KB)
        shm = fs.store.shm
        assert shm is not None and shm.cached_blocks() >= 1
        granted = metrics().counter("Worker.ShmLeasesGranted").count
        with fs.open_file("/shm-cached") as f:
            assert f.block_stream(0).last_source != "UFS"
            f.block_stream(0).pread(0, KB)
        # cache hit: the re-open took no new lease
        assert metrics().counter("Worker.ShmLeasesGranted").count == \
            granted

    def test_stream_outliving_its_cached_segment_reopens(self, fs):
        """A file stream held across the transport's LRU turnover (a
        loader keeps one per file over epochs, with more blocks than
        ``segment.cache.max``) opens the block again — the released
        segment used to be served as an EMPTY block, silently. A block
        stream held directly raises instead of reading empty."""
        data = _patterned(4 * KB, 7)
        fs.write_all("/shm-outlive", data, write_type="MUST_CACHE")
        f = fs.open_file("/shm-outlive")
        held = f.block_stream(0)
        assert bytes(held.numpy_view()) == data
        fs.store.shm.close()  # what LRU turnover does to every segment
        assert held.stale()
        for read in (held.numpy_view, held.memoryview,
                     lambda: held.pread(0, KB),
                     lambda: held.pread_many([0, KB], [16, 16])):
            with pytest.raises(ShmSegmentUnavailableError):
                read()
        fresh = f.block_stream(0)
        assert fresh is not held and not fresh.stale()
        assert bytes(fresh.numpy_view()) == data
        fs.store.shm.close()
        assert f.pread(KB, KB) == data[KB:2 * KB]
        f.close()

    def test_a_lower_tier_block_rides_the_lease_plane(self, cluster):
        """A block committed to the SSD tier of a same-host worker is
        leased and mapped like a MEM-tier one, byte for byte."""
        data = _patterned(BLOCK, 0x55D)
        f2 = cluster.file_system()
        try:
            f2.write_all("/shm-ssd", data, write_type="MUST_CACHE",
                         tier="SSD")
            bid = f2.get_status("/shm-ssd").block_ids[0]
            store = cluster.workers[0].worker.store
            assert store.get_block_meta(bid).tier_alias == "SSD"
            read = metrics().counter("Client.BytesRead.shm").count
            with f2.open_file("/shm-ssd") as f:
                bs = f.block_stream(0)
                assert type(bs).__name__ == "ShmBlockInStream"
                nv = bs.numpy_view()
                assert nv.tobytes() == data
                del nv
            assert metrics().counter("Client.BytesRead.shm").count == \
                read + len(data)
            assert bid in store.shm_leased_blocks
        finally:
            f2.close()

    def test_worker_session_cleanup_releases_leases(self, cluster):
        f2 = cluster.file_system()
        f2.write_all("/shm-bye", b"z" * KB, write_type="MUST_CACHE")
        with f2.open_file("/shm-bye") as f:
            f.block_stream(0).pread(0, KB)
        worker = cluster.workers[0].worker
        leased = worker.shm_store.stats()["live_leases"]
        assert leased >= 1
        f2.close()  # graceful: cleanup_session sweeps this client
        by_session = worker.shm_store.stats()["sessions"]
        assert worker.shm_store.stats()["live_leases"] < leased or \
            by_session >= 0  # other module clients may hold leases


# ------------------------------------------------- scatter/gather sweep
class TestScatterGather:
    def _remote_fs(self, cluster):
        conf = cluster.conf.copy()
        conf.set(Keys.USER_SHORT_CIRCUIT_ENABLED, False)
        from alluxio_tpu.client.file_system import FileSystem

        return FileSystem(cluster.master.address, conf=conf)

    def test_property_sweep_matches_per_op(self, cluster):
        """Seeded sweep of offset/size patterns — ragged, overlapping,
        zero-length, end-clamped — batched result must equal the
        per-op loop slice for slice."""
        data = _patterned(BLOCK, 0x5EED)
        rfs = self._remote_fs(cluster)
        try:
            rfs.write_all("/sg-sweep", data, write_type="MUST_CACHE")
            rng = random.Random(0x5EED)
            with rfs.open_file("/sg-sweep") as f:
                bs = f.block_stream(0)
                assert type(bs).__name__ == "GrpcBlockInStream"
                for trial in range(6):
                    ops = rng.randrange(2, 40)
                    offsets = [rng.randrange(0, BLOCK)
                               for _ in range(ops)]
                    sizes = [rng.choice((0, 1, 7, 512, 4096))
                             for _ in range(ops)]
                    got = bs.pread_many(offsets, sizes)
                    want = [data[o:o + s] if s else b""
                            for o, s in zip(offsets, sizes)]
                    # end-clamp: ops that run past the block truncate
                    want = [w[:max(0, BLOCK - o)][:s] for w, o, s
                            in zip(want, offsets, sizes)]
                    assert got == want, f"trial {trial}"
        finally:
            rfs.close()

    def test_batched_counters_and_fallback(self, cluster):
        data = _patterned(BLOCK, 0xC0)
        rfs = self._remote_fs(cluster)
        try:
            rfs.write_all("/sg-count", data, write_type="MUST_CACHE")
            m = metrics()
            with rfs.open_file("/sg-count") as f:
                bs = f.block_stream(0)
                before = m.counter("Client.BatchReadBatches").count
                bs.pread_many([0, 100, 200], [64, 64, 64])
                assert m.counter("Client.BatchReadBatches").count == \
                    before + 1
                # an op above max_op_bytes makes the batch ineligible:
                # per-op path, same bytes, no batch RPC
                before = m.counter("Client.BatchReadBatches").count
                got = bs.pread_many([0, 128], [96 * KB, 64])
                assert got == [data[:96 * KB], data[128:192]]
                assert m.counter("Client.BatchReadBatches").count == \
                    before
        finally:
            rfs.close()

    def test_read_many_rpc_validates(self, cluster):
        from alluxio_tpu.utils.exceptions import InvalidArgumentError

        rfs = self._remote_fs(cluster)
        try:
            rfs.write_all("/sg-rpc", b"q" * KB, write_type="MUST_CACHE")
            info = rfs.get_status("/sg-rpc")
            worker = rfs.store.worker_client(
                rfs.store._live_workers()[0].address)
            bid = info.block_ids[0]
            resp = worker.read_many(bid, [0, 512], [4, 4])
            assert resp["lengths"] == [4, 4]
            assert bytes(resp["data"]) == b"qqqqqqqq"
            with pytest.raises(InvalidArgumentError):
                worker.read_many(bid, [0, 1], [4])  # ragged request
        finally:
            rfs.close()


# -------------------------------------------------- disabled-path parity
class TestDisabledByteIdentity:
    def test_disabled_path_is_byte_identical(self, cluster):
        """`atpu.user.short.circuit.enabled=false` + batching off: the
        remote rung must serve the exact bytes of the same-host plane —
        over real gRPC, not mocks."""
        data = _patterned(2 * BLOCK, 0xD15)
        enabled = cluster.file_system()
        conf = cluster.conf.copy()
        conf.set(Keys.USER_SHORT_CIRCUIT_ENABLED, False)
        conf.set(Keys.USER_BATCH_READ_ENABLED, False)
        from alluxio_tpu.client.file_system import FileSystem

        disabled = FileSystem(cluster.master.address, conf=conf)
        try:
            enabled.write_all("/parity", data, write_type="MUST_CACHE")
            assert disabled.read_all("/parity") == data
            assert enabled.read_all("/parity") == data
            assert disabled.store.shm is None
            with disabled.open_file("/parity") as f:
                bs = f.block_stream(0)
                assert type(bs).__name__ == "GrpcBlockInStream"
                # pread_many still works — the per-op default path
                got = bs.pread_many([0, 5, BLOCK - 3], [4, 4, 10])
                assert got == [data[:4], data[5:9],
                               data[BLOCK - 3:BLOCK]]
        finally:
            disabled.close()
            enabled.close()


# --------------------------------------------------------------- chaos
class TestChaosFallback:
    @pytest.fixture(autouse=True)
    def _clean_faults(self):
        faults.injector().reset()
        yield
        faults.injector().reset()

    def test_map_fault_falls_back_and_still_serves(self, cluster):
        """Injected mmap failure: the read must transparently fall one
        rung (remote) and return the bytes."""
        data = _patterned(KB, 0xFA)
        f2 = cluster.file_system()
        try:
            f2.write_all("/chaos-map", data, write_type="MUST_CACHE")
            m = metrics()
            failures = m.counter("Client.ShmMapFailures").count
            faults.injector().set(shm_map_error_rate=1.0)
            with f2.open_file("/chaos-map") as f:
                bs = f.block_stream(0)
                assert bs.pread(0, KB) == data
                assert type(bs).__name__ != "ShmBlockInStream"
            assert m.counter("Client.ShmMapFailures").count > failures
            assert faults.injector().injected.get("shm_map_error", 0) > 0
        finally:
            f2.close()

    def test_a_failed_map_goes_remote_in_one_step(self, cluster,
                                                  monkeypatch):
        """After a failed map the ladder does not lease and map the same
        file again: ONE lease, given back at once, then the remote
        stream."""
        data = _patterned(KB, 0xFC)
        f2 = cluster.file_system()
        shm_store = cluster.workers[0].worker.shm_store
        opened, released, leases = [], [], {}
        real_open, real_release = shm_store.open, shm_store.release

        def open_(session_id, block_id):
            lease = real_open(session_id, block_id)
            opened.append(block_id)
            leases[lease["lease_id"]] = block_id
            return lease

        def release(session_id, lease_id):
            released.append(leases.get(lease_id))
            return real_release(session_id, lease_id)

        monkeypatch.setattr(shm_store, "open", open_)
        monkeypatch.setattr(shm_store, "release", release)
        try:
            f2.write_all("/chaos-once", data, write_type="MUST_CACHE")
            bid = f2.get_status("/chaos-once").block_ids[0]
            faults.injector().set(shm_map_error_rate=1.0)
            with f2.open_file("/chaos-once") as f:
                bs = f.block_stream(0)
                assert type(bs).__name__ == "GrpcBlockInStream"
                assert bs.pread(0, KB) == data
            assert opened.count(bid) == 1 and released.count(bid) == 1
        finally:
            f2.close()

    def test_lease_deny_falls_back_and_still_serves(self, cluster):
        data = _patterned(KB, 0xFB)
        f2 = cluster.file_system()
        try:
            f2.write_all("/chaos-deny", data, write_type="MUST_CACHE")
            m = metrics()
            denied = m.counter("Worker.ShmLeasesDenied").count
            faults.injector().set(shm_lease_deny_rate=1.0)
            with f2.open_file("/chaos-deny") as f:
                bs = f.block_stream(0)
                assert bs.pread(0, KB) == data
                assert type(bs).__name__ != "ShmBlockInStream"
            assert m.counter("Worker.ShmLeasesDenied").count > denied
        finally:
            f2.close()

    def test_fault_keys_configure_from_conf(self):
        from alluxio_tpu.conf import Configuration

        conf = Configuration()
        conf.set(Keys.DEBUG_FAULT_SHM_MAP_ERROR_RATE, 0.25)
        conf.set(Keys.DEBUG_FAULT_SHM_LEASE_DENY_RATE, 0.5)
        inj = faults.injector()
        inj.configure(conf)
        assert inj.shm_map_error_rate == 0.25
        assert inj.shm_lease_deny_rate == 0.5
        # deterministic pacing: rate 0.5 fails every other op
        outcomes = [inj.take_shm_lease_deny("w0") for _ in range(4)]
        assert outcomes.count(True) == 2


# ------------------------------------- eviction off the opener's thread
RELEASER = "atpu-shm-release"


class _RecordingWorker:
    """A worker's lease plane, faked: one file a block under ``base``,
    every call remembered with the thread that made it (and, given
    ``cached``, with what it read when a lease was asked for).
    ``gate``, when given, holds ``shm_release`` on the RELEASER thread
    until it is set; ``raise_first`` makes the first release raise."""

    def __init__(self, base, *, gate=None, raise_first=False,
                 cached=None):
        self._base = base
        self._gate = gate
        self._raise = raise_first
        self._cached = cached  # how many segments the cache holds now
        self._ids = iter(range(1, 1 << 30))
        self.events = []  # (what, block or lease id, thread name)
        self.lease_of = {}  # lease id -> block id
        self.out = set()  # leases granted and not given back

    def data(self, block_id):
        return bytes([block_id % 251 + 1]) * (4 * KB)

    def shm_open(self, session_id, block_id):
        path = self._base / f"b{block_id}"
        if not path.exists():
            path.write_bytes(self.data(block_id))
        lease_id = next(self._ids)
        self.lease_of[lease_id] = block_id
        self.out.add(lease_id)
        me = threading.current_thread().name
        self.events.append(("shm_open", block_id, me) if self._cached is None
                           else ("shm_open", block_id, me, self._cached()))
        return {"lease_id": lease_id, "path": str(path),
                "length": 4 * KB, "ttl_s": 60.0}

    def shm_release(self, session_id, lease_id):
        me = threading.current_thread().name
        if self._gate is not None and me == RELEASER:
            assert self._gate.wait(30.0)
        self.events.append(("shm_release", self.lease_of[lease_id], me))
        self.out.discard(lease_id)
        if self._raise:
            self._raise = False
            raise RuntimeError("injected: release failed")

    def shm_renew(self, session_id, lease_id):
        return {"ok": True, "ttl_s": 60.0}

    def released(self):
        return [b for what, b, _t in self.events if what == "shm_release"]


def _transport(cache_max):
    from alluxio_tpu.client.shm_transport import ShmTransport

    return ShmTransport(SESSION, cache_max=cache_max)


def _evict_counts():
    snap = metrics().snapshot()
    return (snap.get("Client.ShmEvictHandoffs", 0),
            snap.get("Client.ShmEvictInline", 0))


class TestEvictionOffTheOpenersThread:
    """A miss at the cache's bound makes room BEFORE it leases, and the
    victim's unmap + release run on the transport's own thread."""

    def test_room_is_made_before_the_lease_and_off_the_openers_thread(
            self, tmp_path, monkeypatch):
        from alluxio_tpu.client.shm_transport import ShmSegment

        t = _transport(2)
        worker = _RecordingWorker(tmp_path, cached=t.cached_blocks)
        me = threading.current_thread().name
        real_close, real_put = ShmSegment.close_map, t._victims.put

        def close_map(seg):
            worker.events.append(("close_map", seg.block_id,
                                  threading.current_thread().name))
            real_close(seg)

        class Q:  # SimpleQueue takes no attribute: stand in front of it
            get = t._victims.get

            @staticmethod
            def put(item):
                if item is not None:
                    worker.events.append(("handoff", item[1].block_id, me))
                real_put(item)

        monkeypatch.setattr(ShmSegment, "close_map", close_map)
        t._victims = Q
        handoffs, inline = _evict_counts()
        try:
            for bid in range(6):
                seg = t.segment(worker, bid)
                assert bytes(seg.view()) == worker.data(bid)
                assert t.cached_blocks() <= 2
                assert t.drain(10.0)  # so fewer than the bound wait
            ev = worker.events
            for bid in range(2, 6):
                # the victim (the block opened two before) is out of
                # the cache (1 left of 2) and handed off BEFORE this
                # block's lease is asked for
                assert ev.index(("handoff", bid - 2, me)) < \
                    ev.index(("shm_open", bid, me, 1))
            # neither half ever ran on the opener's thread
            assert [e for e in ev if e[0] in ("close_map", "shm_release")
                    and e[2] == me] == []
            assert [e[1:] for e in ev if e[0] == "close_map"] == \
                [(b, RELEASER) for b in range(4)]
            assert [e[1:] for e in ev if e[0] == "shm_release"] == \
                [(b, RELEASER) for b in range(4)]
            now = _evict_counts()
            assert (now[0] - handoffs, now[1] - inline) == (4, 0)
        finally:
            t.close()

    def test_a_lease_that_fails_still_hands_its_victim_off(self, tmp_path):
        """The victim left the cache for a lease that was then denied:
        it is released all the same and the cache stands one under its
        bound, which the next open fills."""
        worker = _RecordingWorker(tmp_path)
        t = _transport(2)
        try:
            t.segment(worker, 0)
            t.segment(worker, 1)
            real_open = worker.shm_open

            def denied(session_id, block_id):
                raise ShmLeaseDeniedError("injected: table full")

            worker.shm_open = denied
            with pytest.raises(ShmLeaseDeniedError):
                t.segment(worker, 2)
            assert t.drain(10.0)
            assert worker.released() == [0] and t.cached_blocks() == 1
            worker.shm_open = real_open
            assert bytes(t.segment(worker, 2).view()) == worker.data(2)
            assert t.drain(10.0)
            assert worker.released() == [0] and t.cached_blocks() == 2
        finally:
            t.close()

    def test_a_slow_release_pushes_the_eviction_back_in_line(
            self, tmp_path):
        from alluxio_tpu.client.shm_transport import _RELEASE_BACKLOG

        gate = threading.Event()
        worker = _RecordingWorker(tmp_path, gate=gate)
        t = _transport(2)
        me = threading.current_thread().name
        handoffs, inline = _evict_counts()
        segs = []
        try:
            for bid in range(12):
                segs.append(t.segment(worker, bid))
                mapped = sum(1 for s in segs if s.mm is not None)
                assert mapped <= 2 + _RELEASE_BACKLOG, bid
                assert t.cached_blocks() <= 2
            # 10 evictions: the first 4 wait behind the held release,
            # every later one the opener released itself
            now = _evict_counts()
            assert now[0] - handoffs == _RELEASE_BACKLOG
            assert now[1] - inline == 10 - _RELEASE_BACKLOG
            assert [e[1] for e in worker.events
                    if e[0] == "shm_release" and e[2] == me] == \
                list(range(_RELEASE_BACKLOG, 10))
            assert not t.drain(0.05)  # still held
            gate.set()
            assert t.drain(10.0)
            assert sorted(worker.released()) == list(range(10))
            assert sum(1 for s in segs if s.mm is not None) == 2
            # the thread keeps up again: the next eviction is a hand-off
            t.segment(worker, 12)
            assert t.drain(10.0)
            assert _evict_counts()[0] - handoffs == _RELEASE_BACKLOG + 1
            assert ("shm_release", 10, RELEASER) in worker.events
        finally:
            gate.set()
            t.close()

    def test_close_joins_the_thread_and_closes_every_map(self, tmp_path):
        before = set(threading.enumerate())
        worker = _RecordingWorker(tmp_path)
        t = _transport(2)
        segs = [t.segment(worker, bid) for bid in range(2)]
        # a transport that evicts nothing starts no thread
        assert set(threading.enumerate()) == before
        segs += [t.segment(worker, bid) for bid in range(2, 5)]
        started = set(threading.enumerate()) - before
        assert [th.name for th in started] == [RELEASER]
        assert all(th.daemon for th in started)
        t.close()
        assert set(threading.enumerate()) == before
        assert not any(th.is_alive() for th in started)
        assert all(s.mm is None and s.dead for s in segs)
        assert t.cached_blocks() == 0
        # what waited went back through the RPC; the two still cached
        # are the session's to return (cleanup_session), as before
        assert sorted(worker.released()) == [0, 1, 2]
        assert sorted(worker.lease_of[x] for x in worker.out) == [3, 4]

    def test_a_transport_that_never_evicted_closes_without_a_thread(
            self, tmp_path):
        before = set(threading.enumerate())
        worker = _RecordingWorker(tmp_path)
        t = _transport(4)
        segs = [t.segment(worker, bid) for bid in range(4)]
        t.segment(worker, 1)  # a hit
        assert t.drain(0.0)  # nothing ever waited
        t.close()
        assert set(threading.enumerate()) == before
        assert all(s.mm is None for s in segs)
        assert worker.released() == []

    @pytest.mark.parametrize("what", ["the_rpc", "the_unmap"])
    def test_a_release_that_raises_does_not_end_the_thread(
            self, tmp_path, monkeypatch, what):
        from alluxio_tpu.client.shm_transport import ShmSegment

        worker = _RecordingWorker(tmp_path, raise_first=what == "the_rpc")
        if what == "the_unmap":
            real_close = ShmSegment.close_map
            failed = []

            def close_map(seg):
                real_close(seg)
                if not failed:
                    failed.append(seg.block_id)
                    raise RuntimeError("injected: unmap failed")

            monkeypatch.setattr(ShmSegment, "close_map", close_map)
        t = _transport(1)
        try:
            t.segment(worker, 0)
            t.segment(worker, 1)  # victim 0: its release raises
            assert t.drain(10.0)
            t.segment(worker, 2)  # victim 1: released all the same
            assert t.drain(10.0)
            assert t._releaser.is_alive()
            # an unmap that raised never reached its RPC (the TTL's)
            assert worker.released() == \
                ([0, 1] if what == "the_rpc" else [1])
        finally:
            t.close()

    def test_a_map_closed_under_a_reader_is_a_released_segment(
            self, tmp_path):
        """The releaser may close a mapping between a reader's look at
        it and its view of it: the typed error the re-open loops
        handle, not the ``ValueError`` of a closed mmap."""
        import numpy as np

        from alluxio_tpu.client.shm_transport import ShmBlockInStream

        worker = _RecordingWorker(tmp_path)
        t = _transport(2)
        try:
            seg = t.segment(worker, 0)
            stream = ShmBlockInStream(t, worker, seg)
            # any other ValueError is the caller's own
            with pytest.raises(ValueError, match="multiple of element"):
                stream.numpy_view(np.dtype("V3"))
            seg.mm.close()  # the look saw it open; the view finds it shut
            for read in (seg.view, stream.numpy_view, stream.memoryview,
                         lambda: stream.pread(0, 16)):
                with pytest.raises(ShmSegmentUnavailableError):
                    read()
        finally:
            t.close()

    @pytest.mark.parametrize("openers", [1, 2])
    def test_no_lease_is_orphaned_after_a_drain(self, cluster, openers):
        """PR 38's invariant, over a real worker, with the releases on
        their own thread: leases granted - given back = segments still
        held, every block byte for byte, one and two opener threads on
        ONE cache of 2."""
        conf = cluster.conf.copy()
        conf.set(Keys.USER_SHM_SEGMENT_CACHE_MAX, 2)
        from alluxio_tpu.client.file_system import FileSystem

        client = FileSystem(cluster.master.address, conf=conf)
        shm_store = cluster.workers[0].worker.shm_store
        n_blocks, rounds = 6, 3
        data = _patterned(n_blocks * BLOCK, 0x39 + openers)
        path = f"/shm-evict-{openers}"
        handoffs, inline = _evict_counts()
        try:
            client.write_all(path, data, write_type="MUST_CACHE")
            base = shm_store.stats()["live_leases"]
            errors = []

            def scan(first):
                try:
                    with client.open_file(path) as f:
                        for k in range(rounds * n_blocks):
                            i = (first + k) % n_blocks
                            want = data[i * BLOCK:(i + 1) * BLOCK]
                            for _try in range(50):
                                # the other opener may push this segment
                                # out between the open and the view: the
                                # loader's re-open loop, in small
                                try:
                                    got = f.block_stream(i) \
                                        .numpy_view().tobytes()
                                    break
                                except ShmSegmentUnavailableError:
                                    continue
                            assert got == want, (first, k)
                except Exception as e:  # noqa: BLE001 - shown below
                    errors.append(repr(e))

            threads = [threading.Thread(target=scan, args=(j * 3,))
                       for j in range(openers)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(120)
            assert not errors, errors
            shm = client.store.shm
            assert shm.drain(10.0)
            held = shm.cached_blocks()
            assert 1 <= held <= 2
            assert shm_store.stats()["live_leases"] - base == held
            now = _evict_counts()
            # a slow box may push some back in line: both ways count
            assert (now[0] - handoffs) + (now[1] - inline) >= \
                rounds * n_blocks - 2
        finally:
            client.close()

    def test_a_block_opened_again_while_its_old_segment_waits(
            self, cluster, monkeypatch):
        conf = cluster.conf.copy()
        conf.set(Keys.USER_SHM_SEGMENT_CACHE_MAX, 2)
        from alluxio_tpu.client.file_system import FileSystem

        client = FileSystem(cluster.master.address, conf=conf)
        shm_store = cluster.workers[0].worker.shm_store
        data = _patterned(3 * BLOCK, 0x396)
        gate = threading.Event()
        try:
            client.write_all("/shm-again", data, write_type="MUST_CACHE")
            base = shm_store.stats()["live_leases"]
            shm = client.store.shm
            real_release = shm._release

            def held_release(worker, seg):
                if threading.current_thread().name == RELEASER:
                    assert gate.wait(30.0)
                real_release(worker, seg)

            monkeypatch.setattr(shm, "_release", held_release)
            with client.open_file("/shm-again") as f, \
                    client.open_file("/shm-again") as g:
                first = f.block_stream(0)
                old = first._seg
                assert first.numpy_view().tobytes() == data[:BLOCK]
                for i in (1, 2):  # 2 pushes block 0's segment out
                    assert f.block_stream(i).numpy_view().tobytes() == \
                        data[i * BLOCK:(i + 1) * BLOCK]
                # handed off, not yet released: mapping and lease live
                assert not shm.drain(0.05)
                assert old.mm is not None and not old.released
                assert shm_store.stats()["live_leases"] - base == 3
                # a stream that holds the old segment reads on through
                # it (mapped and leased until the thread gets to it)
                assert f.block_stream(0) is first
                assert first.numpy_view().tobytes() == data[:BLOCK]
                # a NEW open asks the cache, which no longer knows the
                # old segment: a fresh lease and a fresh map, the right
                # bytes
                again = g.block_stream(0)
                assert again._seg is not old
                assert again._seg.lease_id != old.lease_id
                assert again.numpy_view().tobytes() == data[:BLOCK]
                gate.set()
                assert shm.drain(10.0)
                assert old.released and first.stale()
                with pytest.raises(ShmSegmentUnavailableError):
                    first.numpy_view()
                assert again.numpy_view().tobytes() == data[:BLOCK]
                held = shm.cached_blocks()
                assert held == 2
                assert shm_store.stats()["live_leases"] - base == held
        finally:
            gate.set()
            client.close()
