"""BlockWorker integration tests: registration, heartbeat delta reporting,
commit-to-master, UFS read-through, async cache, pin-list sync.

Reference analogues: ``core/server/worker/src/test/java/alluxio/worker/block/
{BlockMasterSyncTest,DefaultBlockWorkerTest}.java``.
"""

import pytest

from alluxio_tpu.conf import Configuration, Keys
from alluxio_tpu.journal import NoopJournalSystem
from alluxio_tpu.master import BlockMaster, FileSystemMaster
from alluxio_tpu.underfs import UfsManager, create_ufs
from alluxio_tpu.utils import ids as id_utils
from alluxio_tpu.worker import BlockWorker, UfsBlockDescriptor
from alluxio_tpu.worker.master_sync import InProcessBlockMasterClient

KB = 1024
SESSION = 99


class InProcessFsMasterClient:
    def __init__(self, fsm):
        self._fsm = fsm

    def get_pinned_file_ids(self):
        return self._fsm.get_pinned_file_ids()


@pytest.fixture()
def cluster(conf, tmp_path):
    """Master + one worker wired in-process."""
    conf.set(Keys.WORKER_RAMDISK_SIZE, 16 * KB)
    journal = NoopJournalSystem()
    bm = BlockMaster(journal)
    fsm = FileSystemMaster(bm, journal, default_block_size=KB)
    fsm.start(str(tmp_path / "root_ufs"))
    worker = BlockWorker(conf, InProcessBlockMasterClient(bm),
                         InProcessFsMasterClient(fsm),
                         ufs_manager=fsm.ufs_manager)
    worker._master_sync.register_with_master()
    yield bm, fsm, worker
    worker.async_cache.close()


def test_register_reports_tiers(cluster):
    bm, fsm, worker = cluster
    infos = bm.get_worker_infos()
    assert len(infos) == 1
    assert set(infos[0].capacity_bytes_on_tiers) == {"MEM", "SSD"}


def test_commit_reaches_master(cluster):
    bm, fsm, worker = cluster
    worker.create_block(SESSION, 100, initial_bytes=KB, tier_alias="MEM")
    with worker.get_temp_writer(SESSION, 100) as w:
        w.append(b"z" * 100)
    worker.commit_block(SESSION, 100)
    info = bm.get_block_info(100)
    assert info.length == 100
    assert info.locations[0].tier_alias == "MEM"


def test_heartbeat_reports_deltas_and_handles_free(cluster):
    bm, fsm, worker = cluster
    # unknown-to-master block: worker commit_block reports it via
    # commit_block RPC, so use the store directly to fake a stale block
    worker.store.create_block(SESSION, 555, initial_bytes=10)
    with worker.store.get_temp_writer(SESSION, 555) as w:
        w.append(b"stale")
    worker.store.commit_block(SESSION, 555)
    assert worker.store.has_block(555)
    worker._master_sync.heartbeat()  # master answers FREE for unknown block
    assert not worker.store.has_block(555)


def test_ufs_read_through_caches(cluster, tmp_path):
    bm, fsm, worker = cluster
    ufs_dir = tmp_path / "ext"
    ufs_dir.mkdir()
    payload = bytes(range(256)) * 4
    (ufs_dir / "obj").write_bytes(payload)
    fsm.mount("/ext", str(ufs_dir))
    st = fsm.get_status("/ext/obj")
    bid = st.block_ids[0]
    mount_id = fsm.mount_table.resolve(
        __import__("alluxio_tpu.utils.uri", fromlist=["AlluxioURI"]
                   ).AlluxioURI("/ext/obj")).mount_id
    desc = UfsBlockDescriptor(block_id=bid, ufs_path=str(ufs_dir / "obj"),
                              offset=0, length=len(payload),
                              mount_id=mount_id)
    data = worker.read_ufs_block(desc, cache=True)
    assert data == payload
    # second read is warm (served from the tiered store)
    with worker.open_reader(bid) as r:
        assert r.read(0, len(payload)) == payload
    # commit from cache fill is local only; heartbeat reports it upward
    worker._master_sync.heartbeat()
    assert len(bm.get_block_info(bid).locations) == 1


def test_async_cache_manager(cluster, tmp_path):
    bm, fsm, worker = cluster
    ufs_dir = tmp_path / "ext2"
    ufs_dir.mkdir()
    (ufs_dir / "f").write_bytes(b"q" * 512)
    fsm.mount("/ext2", str(ufs_dir))
    st = fsm.get_status("/ext2/f")
    from alluxio_tpu.utils.uri import AlluxioURI

    mount_id = fsm.mount_table.resolve(AlluxioURI("/ext2/f")).mount_id
    desc = UfsBlockDescriptor(block_id=st.block_ids[0],
                              ufs_path=str(ufs_dir / "f"), offset=0,
                              length=512, mount_id=mount_id)
    assert worker.async_cache.submit(desc)
    worker.async_cache.wait_idle()
    assert worker.store.has_block(st.block_ids[0])
    assert not worker.async_cache.submit(desc)  # already cached


def test_pin_list_sync(cluster):
    bm, fsm, worker = cluster
    info = fsm.create_file("/pinme")
    bid = fsm.get_new_block_id_for_file("/pinme")
    worker.create_block(SESSION, bid, initial_bytes=10)
    with worker.get_temp_writer(SESSION, bid) as w:
        w.append(b"0123456789")
    worker.commit_block(SESSION, bid)
    fsm.complete_file("/pinme")
    fsm.set_attribute("/pinme", pinned=True)
    worker._pin_sync.heartbeat()
    assert worker.store.master_pinned_blocks == {bid}
    fsm.set_attribute("/pinme", pinned=False)
    worker._pin_sync.heartbeat()
    assert worker.store.master_pinned_blocks == set()


def test_lease_pins_a_lower_tier_block_until_released(cluster):
    """The lease plane's pin on an SSD-tier block: eviction pressure
    skips the block while it is leased and takes it after release."""
    bm, fsm, worker = cluster
    size = 16 * KB  # the SSD tier is 4x the 16 KB ramdisk: four blocks

    def put(bid):
        worker.create_block(SESSION, bid, initial_bytes=size,
                            tier_alias="SSD")
        with worker.get_temp_writer(SESSION, bid) as w:
            w.append(bytes([bid]) * size)
        worker.commit_block(SESSION, bid)

    def ssd():
        return set(worker.store.block_report()["SSD"])

    for bid in (1, 2, 3, 4):
        put(bid)
    lease = worker.shm_store.open(SESSION, 1)
    with open(lease["path"], "rb") as f:  # a client would mmap this
        assert f.read() == bytes([1]) * size
    for bid in (2, 3, 4):  # the grant touched 1: make it coldest again
        worker.store.access_block(bid)
    put(5)  # must evict: 1 is the coldest, and leased
    assert ssd() == {1, 3, 4, 5}
    assert worker.shm_store.release(SESSION, lease["lease_id"])
    put(6)  # 1 is still the coldest, and no longer shielded
    assert ssd() == {3, 4, 5, 6}
    assert not worker.store.has_block(1)
