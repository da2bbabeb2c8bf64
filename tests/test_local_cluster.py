"""End-to-end cluster tests: FileSystem client against master + workers
over real gRPC (the reference's ``LocalAlluxioCluster``-based integration
tests, e.g. ``tests/src/test/java/alluxio/client/fs/FileSystemIntegrationTest``).
"""

import os

import pytest

from alluxio_tpu.client.streams import WriteType
from alluxio_tpu.conf import Keys
from alluxio_tpu.minicluster import LocalCluster

KB = 1024
BLOCK = 64 * KB


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    base = str(tmp_path_factory.mktemp("cluster"))
    with LocalCluster(base, num_workers=1, block_size=BLOCK,
                      worker_mem_bytes=4 * 1024 * KB) as c:
        yield c


@pytest.fixture(scope="module")
def fs(cluster):
    f = cluster.file_system()
    yield f
    f.close()


class TestStreamedListing:
    def test_iter_status_batches_whole_directory(self, fs):
        """Partial-response listing (reference: streamed ListStatus,
        ``file_system_master.proto:475-590``): a directory larger than
        the batch size arrives complete, in order, over several
        server-side batches."""
        fs.create_directory("/stream-ls", recursive=True)
        for i in range(23):
            fs.create_directory(f"/stream-ls/d-{i:03d}")
        got = [i.name for i in
               fs.fs_master.iter_status("/stream-ls", batch_size=5)]
        assert got == [f"d-{i:03d}" for i in range(23)]
        # empty dir still terminates cleanly
        fs.create_directory("/stream-ls-empty")
        assert list(fs.fs_master.iter_status("/stream-ls-empty")) == []
        # a file path yields its own status, like list_status
        fs.write_all("/stream-one", b"x")
        one = list(fs.fs_master.iter_status("/stream-one"))
        assert len(one) == 1 and one[0].name == "stream-one"

    def test_iter_status_recursive_uses_row_batches(self, fs):
        """recursive=True rides the row-dict fallback (columnar is
        non-recursive only) and must surface the whole subtree."""
        fs.create_directory("/stream-rec/a/b", recursive=True)
        fs.write_all("/stream-rec/a/f1", b"x")
        fs.write_all("/stream-rec/a/b/f2", b"x")
        got = sorted(i.path for i in fs.fs_master.iter_status(
            "/stream-rec", recursive=True, batch_size=2))
        assert got == ["/stream-rec/a", "/stream-rec/a/b",
                       "/stream-rec/a/b/f2", "/stream-rec/a/f1"]

    def test_iter_status_decodes_row_dict_batches(self, fs):
        """A pre-columnar server ships {"infos": [...]} batches; the
        client iterator must still decode them (mixed-version
        cluster)."""
        fs.create_directory("/stream-compat", recursive=True)
        fs.write_all("/stream-compat/f", b"x")
        real = fs.fs_master._channel.call_stream

        def no_columnar(service, method, request):
            req = dict(request)
            req.pop("columnar", None)  # old server ignores the flag
            return real(service, method, req)

        from unittest import mock

        with mock.patch.object(fs.fs_master._channel, "call_stream",
                               side_effect=no_columnar):
            got = [i.name for i in
                   fs.fs_master.iter_status("/stream-compat")]
        assert got == ["f"]


class TestEndToEnd:
    def test_write_read_roundtrip(self, fs):
        payload = bytes(range(256)) * 1000  # 256000 B -> 4 blocks
        fs.write_all("/rt", payload, write_type=WriteType.MUST_CACHE)
        assert fs.read_all("/rt") == payload
        st = fs.get_status("/rt")
        assert st.completed and st.length == len(payload)
        assert len(st.block_ids) == 4

    def test_short_circuit_read_is_mmap(self, fs):
        fs.write_all("/sc", b"short circuit " * 100,
                     write_type=WriteType.MUST_CACHE)
        with fs.open_file("/sc") as f:
            stream = f.block_stream(0)
            assert stream.source == "LOCAL"
            view = stream.numpy_view()
            assert bytes(view[:13]) == b"short circuit"
            assert f.read(13) == b"short circuit"

    def test_seek_and_pread(self, fs):
        data = bytes(range(256)) * 600  # crosses block boundaries
        fs.write_all("/seek", data, write_type=WriteType.MUST_CACHE)
        with fs.open_file("/seek") as f:
            f.seek(BLOCK - 10)
            assert f.read(20) == data[BLOCK - 10:BLOCK + 10]
            assert f.pread(100, 10) == data[100:110]
            assert f.tell() == BLOCK + 10

    def test_cold_read_through_ufs(self, fs, cluster):
        # drop a file straight into the root UFS: metadata loads on access,
        # data cold-reads through a worker and gets cached
        root_ufs = os.path.join(cluster.conf.get(Keys.HOME), "underFSStorage")
        payload = b"cold data " * 5000
        with open(os.path.join(root_ufs, "colddata"), "wb") as f:
            f.write(payload)
        assert fs.read_all("/colddata") == payload
        st = fs.get_status("/colddata")
        assert st.persisted
        # warm now: block report contains its blocks after heartbeat
        cluster.workers[0].worker._master_sync.heartbeat()
        st2 = fs.get_status("/colddata")
        assert st2.in_memory_percentage == 100

    def test_cache_through_persists_to_ufs(self, fs, cluster):
        payload = b"durable " * 1000
        fs.write_all("/persisted", payload, write_type=WriteType.CACHE_THROUGH)
        st = fs.get_status("/persisted")
        assert st.persisted
        assert os.path.exists(st.ufs_path)
        with open(st.ufs_path, "rb") as f:
            assert f.read() == payload

    def test_through_skips_cache(self, fs, cluster):
        payload = b"ufs only " * 1000
        fs.write_all("/through", payload, write_type=WriteType.THROUGH)
        st = fs.get_status("/through")
        assert st.persisted
        # two ticks: one receives the FREE command, the next reports the
        # removal back (reference heartbeat protocol)
        cluster.workers[0].worker._master_sync.heartbeat()
        cluster.workers[0].worker._master_sync.heartbeat()
        assert fs.get_status("/through").in_memory_percentage == 0
        assert fs.read_all("/through") == payload  # re-readable from UFS

    def test_must_cache_not_persisted(self, fs):
        fs.write_all("/memonly", b"x" * 100, write_type=WriteType.MUST_CACHE)
        assert not fs.get_status("/memonly").persisted

    def test_free_then_reread_from_ufs(self, fs, cluster):
        payload = b"freeable " * 2000
        fs.write_all("/freeme", payload, write_type=WriteType.CACHE_THROUGH)
        freed = fs.free("/freeme")
        assert freed
        cluster.workers[0].worker._master_sync.heartbeat()
        assert fs.read_all("/freeme") == payload  # cold path again

    def test_typed_errors_cross_rpc(self, fs):
        from alluxio_tpu.utils.exceptions import (
            FileAlreadyExistsError, FileDoesNotExistError,
        )

        with pytest.raises(FileDoesNotExistError):
            fs.get_status("/no/such/path")
        fs.write_all("/dup", b"1", write_type=WriteType.MUST_CACHE)
        with pytest.raises(FileAlreadyExistsError):
            fs.create_file("/dup")

    def test_rename_delete_visible_through_client(self, fs):
        fs.write_all("/mv_src", b"1", write_type=WriteType.MUST_CACHE)
        fs.rename("/mv_src", "/mv_dst")
        assert fs.exists("/mv_dst") and not fs.exists("/mv_src")
        fs.delete("/mv_dst")
        assert not fs.exists("/mv_dst")

    def test_multi_worker_scale_out(self, cluster, fs):
        handle = cluster.add_worker()
        try:
            infos = fs.block_master.get_worker_infos()
            assert len(infos) == 2
        finally:
            pass  # cluster teardown stops it

    def test_mount_mem_ufs_end_to_end(self, fs):
        from alluxio_tpu.underfs import create_ufs

        ufs = create_ufs("mem://e2e/")
        ufs.mkdirs("mem://e2e/dir")
        with ufs.create("mem://e2e/dir/obj") as f:
            f.write(b"object bytes")
        fs.mount("/objstore", "mem://e2e/dir")
        assert fs.read_all("/objstore/obj") == b"object bytes"


class TestClientPageCache:
    def test_caching_stream_random_reads(self, tmp_path, cluster):
        conf = cluster.conf.copy()
        conf.set(Keys.USER_CLIENT_CACHE_ENABLED, True)
        conf.set(Keys.USER_CLIENT_CACHE_DIR, str(tmp_path / "pc"))
        conf.set(Keys.USER_CLIENT_CACHE_PAGE_SIZE, 4 * KB)
        conf.set(Keys.USER_CLIENT_CACHE_SIZE, 1024 * KB)
        from alluxio_tpu.client.file_system import FileSystem

        fs2 = FileSystem(cluster.master.address, conf=conf)
        try:
            data = bytes(range(256)) * 400
            fs2.write_all("/paged", data, write_type=WriteType.MUST_CACHE)
            with fs2.open_file("/paged") as f:
                assert f.pread(5000, 16) == data[5000:5016]
                assert f.pread(5008, 16) == data[5008:5024]  # same page, hit
                assert f.pread(90000, 16) == data[90000:90016]
            from alluxio_tpu.metrics import metrics

            assert metrics().counter("Client.PageCacheHits").count >= 1
        finally:
            fs2.close()


class TestFailedWorkerRetry:
    def test_read_fails_over_to_replica(self, tmp_path):
        """Regression: a worker dying mid-service must not fail reads of
        blocks that have a healthy replica elsewhere (failed-worker
        memory + retry, reference AlluxioFileInStream :94-95)."""
        with LocalCluster(str(tmp_path), num_workers=2,
                          block_size=BLOCK) as c:
            fs = c.file_system()
            payload = b"failover" * 4096
            fs.write_all("/fo", payload, write_type=WriteType.MUST_CACHE)
            # copy the block to the second worker so a replica exists
            fbis = c.fs_client().get_file_block_info_list("/fo")
            holder_keys = {loc.address.key()
                           for fbi in fbis
                           for loc in fbi.block_info.locations}
            target = next(i for i, w in enumerate(c.workers)
                          if f"localhost:{w.port}" not in holder_keys)
            src = next(i for i in range(len(c.workers)) if i != target)
            for fbi in fbis:
                bid = fbi.block_info.block_id
                data = c.worker_client(src).read_block_bytes(bid)
                c.worker_client(target).write_block(
                    bid, session_id=1, data=data)
            # kill the original holder
            dead = c.workers[src].worker.address
            c.workers[src].stop()
            fs2 = c.file_system()
            # every rung retries the dead worker for its whole budget
            # (30 s each, two minutes in all) before the replica is
            # asked; that long, another test process's worker can take
            # the freed port and answer for it. Keep the window short.
            for client in (fs, fs2):  # fs: its close() asks it too
                client.store.worker_client(dead)._retry_duration_s = 1.0
            assert fs2.read_all("/fo") == payload
            fs2.close()
            fs.close()


class TestHeartbeatlessWorkerTimeout:
    """Regression for the bench worker-expiry bug: a heartbeat-less
    LocalCluster must not let the lost-worker detector expire a healthy
    worker (no heartbeat loop means liveness is unknowable, and no
    re-register command can ever be delivered)."""

    # conf is fully decided in __init__ — no cluster boot needed

    def test_hb_off_cluster_defaults_to_unexpiring_workers(self, tmp_path):
        c = LocalCluster(str(tmp_path), num_workers=1)
        assert c.conf.get_ms(Keys.MASTER_WORKER_TIMEOUT) >= \
            1000 * 60 * 10_000

    def test_explicit_timeout_override_still_wins(self, tmp_path):
        c = LocalCluster(str(tmp_path), num_workers=1,
                         conf_overrides={Keys.MASTER_WORKER_TIMEOUT: "2s"})
        assert c.conf.get_ms(Keys.MASTER_WORKER_TIMEOUT) == 2000

    def test_hb_on_cluster_keeps_normal_timeout(self, tmp_path):
        c = LocalCluster(str(tmp_path), num_workers=1,
                         start_worker_heartbeats=True)
        # the 5-minute reference default, not the hb-off guard value
        assert c.conf.get_ms(Keys.MASTER_WORKER_TIMEOUT) == 5 * 60 * 1000
