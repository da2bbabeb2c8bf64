"""One status RPC for a file list (``get_status_many``): per path it gives
what ``get_status`` gives (the same ``FileInfo``, the same typed error,
permission check, sync, load on access and audit record), over both
routes, under one stamp; and the job-start callers make ONE call where
they made one a path. CPU only: answers and counts, never a speed."""

from __future__ import annotations

import logging
import math
import os
import threading
import time
import types

import pytest

from alluxio_tpu.client import file_system as file_system_mod
from alluxio_tpu.client.file_system import FileSystem
from alluxio_tpu.conf import Configuration, Keys
from alluxio_tpu.metrics import metrics
from alluxio_tpu.minicluster import LocalCluster
from alluxio_tpu.rpc.clients import FsMasterClient
from alluxio_tpu.security.authentication import USER_KEY
from alluxio_tpu.utils.exceptions import (
    FileDoesNotExistError, PermissionDeniedError,
)
from alluxio_tpu.utils.tracing import set_tracing_enabled, tracer

BLOCK = 64 * 1024
ROUTES = {"fastpath": True, "grpc": False}


def _count(name: str) -> float:
    return metrics().snapshot().get(name, 0)


def _served(method: str) -> float:
    """Calls of ``method`` the master served, over both routes."""
    return sum(_count(f"Master.RpcServed.{route}.{method}")
               for route in ROUTES)


@pytest.fixture()
def cluster(tmp_path):
    with LocalCluster(str(tmp_path), num_workers=1, block_size=BLOCK,
                      start_worker_heartbeats=True) as c:
        yield c


@pytest.fixture()
def tree(cluster, tmp_path):
    """Files of two directories, a nested mount and a 0700 directory."""
    fs = cluster.file_system()
    for i in range(6):
        fs.write_all(f"/d/f{i}", bytes([i]) * (BLOCK + i))
    fs.write_all("/e/g", b"g" * 10)
    fs.write_all("/top", b"t")
    ufs = tmp_path / "nested-ufs"
    ufs.mkdir()
    (ufs / "in-ufs.bin").write_bytes(b"u" * 77)
    fs.mount("/d/mnt", str(ufs))
    fs.get_status("/d/mnt/in-ufs.bin")  # loaded: the mount's mtime settles
    fs.write_all("/private/secret", b"s")
    fs.set_attribute("/private", owner="alice", mode=0o700)
    return fs


def _as(cluster, user: str, **kw) -> FsMasterClient:
    return FsMasterClient(cluster.master.address,
                          metadata=((USER_KEY, user),), **kw)


def _fs_as(cluster, user: str) -> FileSystem:
    conf = Configuration(load_env=False)
    conf.set(Keys.SECURITY_LOGIN_USERNAME, user)
    return FileSystem(cluster.master.address, conf=conf)


class TestAnswers:
    @pytest.mark.parametrize("route", sorted(ROUTES))
    def test_order_and_duplicates_over_both_routes(self, cluster, tree,
                                                   route):
        """Request order, duplicates, the root, a directory, a mount
        point and a file under it: each answer IS that path's
        ``get_status``; the route asked for served the one call."""
        paths = ["/d/f3", "/d/f1", "/e/g", "/d/f3", "/top", "/", "/d",
                 "/d/mnt", "/d/mnt/in-ufs.bin", "/d/f1"]
        client = FsMasterClient(cluster.master.address,
                                fastpath=ROUTES[route])
        before = _count(f"Master.RpcServed.{route}.get_status_many")
        answers = client.get_status_many(paths)
        assert _count(f"Master.RpcServed.{route}.get_status_many") \
            == before + 1
        assert answers == [client.get_status(p) for p in paths]
        assert [a.path for a in answers] == paths
        assert answers[7].mount_point and not answers[0].mount_point
        assert answers[8].ufs_path.endswith("nested-ufs/in-ufs.bin")

    def test_an_empty_list_is_answered_without_a_call(self, tree):
        before = _served("get_status_many")
        assert tree.get_status_many([]) == []
        assert _served("get_status_many") == before

    @pytest.mark.parametrize("bad, error", [
        ("/d/nope", FileDoesNotExistError),
        ("/nope/deeper/x", FileDoesNotExistError),
        ("/top/under-a-file", FileDoesNotExistError),
        ("/private/secret", PermissionDeniedError),
        ("/private/absent", PermissionDeniedError),
    ])
    def test_a_failed_path_carries_its_own_error_and_the_rest_answer(
            self, cluster, tree, bad, error):
        bob = _as(cluster, "bob", retry_duration_s=0.0)
        with pytest.raises(error) as single:
            bob.get_status(bad)
        answers = bob.get_status_many(["/d/f0", bad, "/e/g", bad])
        assert [type(a) for a in answers[1::2]] == [error, error]
        assert str(answers[1]) == str(single.value)
        assert answers[0] == bob.get_status("/d/f0")
        assert answers[2] == bob.get_status("/e/g")

    @pytest.mark.parametrize("paths, error", [
        (["/d/f0", "/d/nope", "/private/secret"], FileDoesNotExistError),
        (["/d/f0", "/private/secret", "/d/nope"], PermissionDeniedError),
    ])
    def test_the_client_raises_the_first_error_in_list_order(
            self, cluster, tree, paths, error):
        fs = _fs_as(cluster, "bob")
        try:
            with pytest.raises(error):
                fs.get_status_many(paths)
            assert fs.get_status_many(paths[:1]) == \
                [fs.get_status(paths[0])]
        finally:
            fs.close()

    def test_the_owner_of_a_0700_directory_is_answered(self, cluster, tree):
        alice = _as(cluster, "alice")
        (answer,) = alice.get_status_many(["/private/secret"])
        assert answer == alice.get_status("/private/secret")

    def test_a_path_absent_from_the_tree_is_loaded_on_access(
            self, cluster, tree):
        root = cluster.fs_client().get_mount_points()[0].ufs_uri
        os.makedirs(os.path.join(root, "oob"))
        for name in ("a.bin", "b.bin"):
            with open(os.path.join(root, "oob", name), "wb") as f:
                f.write(name.encode() * 5)
        client = cluster.fs_client()
        a, missing, b = client.get_status_many(
            ["/oob/a.bin", "/oob/c.bin", "/oob/b.bin"])
        assert isinstance(missing, FileDoesNotExistError)
        assert (a.length, b.length) == (25, 25) and a.persisted
        assert a == client.get_status("/oob/a.bin")

    def test_each_path_is_synced_at_the_callers_interval(self, cluster,
                                                         tree):
        tree.write_all("/mut.txt", b"version-1", write_type="CACHE_THROUGH")
        root = cluster.fs_client().get_mount_points()[0].ufs_uri
        time.sleep(0.05)  # the mtime must move
        with open(os.path.join(root, "mut.txt"), "wb") as f:
            f.write(b"version-2-different")
        client = cluster.fs_client()
        stale, = client.get_status_many(["/mut.txt"])
        assert stale.length == len(b"version-1")
        fresh, other = client.get_status_many(["/mut.txt", "/d/f0"],
                                              sync_interval_ms=0)
        assert fresh.length == len(b"version-2-different")
        assert other.path == "/d/f0"

    def test_one_audit_record_a_path(self, cluster, tree, caplog):
        paths = ["/d/f0", "/private/secret", "/d/nope", "/d/f0"]
        with caplog.at_level(logging.INFO, logger="alluxio_tpu.audit"):
            _as(cluster, "bob", retry_duration_s=0.0).get_status_many(paths)

            def records():
                return [r.message for r in caplog.records
                        if "ugi=bob" in r.message]

            deadline = time.monotonic() + 3
            while len(records()) < len(paths) and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
        got = records()
        assert len(got) == len(paths)
        assert all("cmd=get_status " in m for m in got)
        assert [m.split("src=")[1].split()[0] for m in got] == paths
        assert ["succeeded=true allowed=true" in m for m in got] == \
            [True, False, False, True]
        assert "succeeded=false allowed=false" in got[1]
        assert "succeeded=false allowed=true" in got[2]

    def test_the_master_answers_wire_dicts_under_one_lock_list_a_parent(
            self, cluster, tree):
        """Six children of one directory and one of another: the batch
        takes two lock lists where the loop takes seven."""
        fsm = cluster.master.fs_master
        paths = [f"/d/f{i}" for i in range(6)] + ["/e/g"]
        taken = []
        real = fsm.inode_tree.lock_path

        def spy(uri, **kw):
            taken.append(uri.path)
            return real(uri, **kw)

        fsm.inode_tree.lock_path = spy
        try:
            answers = fsm.get_status_many(paths)
        finally:
            del fsm.inode_tree.lock_path
        assert taken == ["/d", "/e"]
        assert answers == [fsm.get_status(p).to_wire() for p in paths]

    def test_lookups_race_renames_and_deletes_without_a_wrong_answer(
            self, cluster, tree):
        """Every answer is a status of the path asked or its own
        FileDoesNotExistError, while a writer renames and deletes in the
        directory the batch holds read-locked."""
        fsm = cluster.master.fs_master
        paths = [f"/d/f{i}" for i in range(6)] + ["/d/moved", "/e/g"]
        stop = threading.Event()
        errors: list = []

        def writer():
            try:
                while not stop.is_set():
                    fsm.rename("/d/f5", "/d/moved")
                    fsm.rename("/d/moved", "/d/f5")
            except Exception as e:  # noqa: BLE001 reported below
                errors.append(e)

        def reader():
            try:
                while not stop.is_set():
                    for path, a in zip(paths, fsm.get_status_many(paths)):
                        if isinstance(a, Exception):
                            assert isinstance(a, FileDoesNotExistError)
                            assert path in ("/d/f5", "/d/moved")
                        else:
                            assert a["path"] == path
            except BaseException as e:  # noqa: BLE001 reported below
                errors.append(e)

        threads = [threading.Thread(target=writer)] + \
            [threading.Thread(target=reader) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=20)
        assert not any(t.is_alive() for t in threads)
        assert errors == []


class TestStampAndCache:
    def test_the_stamp_is_taken_before_the_first_lookup(self, cluster,
                                                        tree):
        """A mutation that lands while the batch is being answered
        carries a version ABOVE the reply's stamp."""
        fsm = cluster.master.fs_master
        real = fsm.get_status_many

        def mutate_first(paths, **kw):
            fsm.create_directory("/made-mid-call")
            return real(paths, **kw)

        before = fsm.invalidations.version
        fsm.get_status_many = mutate_first
        try:
            _answers, stamp = cluster.fs_client().get_status_many(
                ["/d/f0", "/d/f1"], want_version=True)
        finally:
            del fsm.get_status_many
        assert stamp == before < fsm.invalidations.version

    def test_a_batched_fill_keeps_the_metadata_cache_coherent(self,
                                                              tmp_path):
        with LocalCluster(str(tmp_path), num_workers=1, conf_overrides={
                Keys.USER_METADATA_CACHE_ENABLED: True}) as c:
            c1, c2 = c.file_system(), c.file_system()
            for name in "abcd":
                c2.write_all(f"/m/{name}", name.encode())
            paths = [f"/m/{name}" for name in "abcd"]
            c1.send_metrics()  # establish the version floor
            assert c1.get_status("/m/a").path == "/m/a"  # one cached
            calls, carried = (_count("Client.StatusBatchCalls"),
                              _count("Client.StatusBatchPaths"))
            hits = _count("Client.MetadataCacheHits")
            first = c1.get_status_many(paths)
            # the hit is answered from the cache, the misses in ONE call
            assert _count("Client.StatusBatchCalls") == calls + 1
            assert _count("Client.StatusBatchPaths") == carried + 3
            assert _count("Client.MetadataCacheHits") == hits + 1
            served = _served("get_status_many")
            assert c1.get_status_many(paths) == first  # all hits now
            assert c1.get_status("/m/c") == first[2]
            assert _served("get_status_many") == served
            # mutate after the call: the invalidation reaches the fill
            c2.rename("/m/b", "/m/b2")
            c2.write_all("/m/e", b"e")
            c1.send_metrics()
            with pytest.raises(FileDoesNotExistError):
                c1.get_status_many(paths)
            again = c1.get_status_many(["/m/a", "/m/b2", "/m/e"])
            assert [i.path for i in again] == ["/m/a", "/m/b2", "/m/e"]
            assert _served("get_status_many") == served + 2


class TestChunks:
    @pytest.mark.parametrize("n", [3, 4, 5, 10])
    def test_a_long_list_goes_as_successive_calls(self, tree, monkeypatch,
                                                  n):
        monkeypatch.setattr(file_system_mod, "STATUS_BATCH_PATHS", 4)
        paths = [f"/d/f{i % 6}" for i in range(n)]
        served, calls, carried = (_served("get_status_many"),
                                  _count("Client.StatusBatchCalls"),
                                  _count("Client.StatusBatchPaths"))
        answers = tree.get_status_many(paths)
        assert [a.path for a in answers] == paths
        assert _served("get_status_many") - served == math.ceil(n / 4)
        assert _count("Client.StatusBatchCalls") - calls == math.ceil(n / 4)
        assert _count("Client.StatusBatchPaths") - carried == n

    def test_an_error_in_a_later_chunk_is_still_raised(self, tree,
                                                       monkeypatch):
        monkeypatch.setattr(file_system_mod, "STATUS_BATCH_PATHS", 2)
        with pytest.raises(FileDoesNotExistError):
            tree.get_status_many(["/d/f0", "/d/f1", "/d/f2", "/d/nope"])


def _reference_plan(fs, paths):
    """What the loop a path gave the loader."""
    infos = {p: fs.get_status(p) for p in paths}
    return ([(p, i) for p in paths
             for i in range(len(infos[p].block_ids))],
            {p: list(infos[p].block_ids) for p in paths})


class TestJobStartCallers:
    @pytest.mark.parametrize("n, chunk", [(1, 1024), (7, 1024), (7, 3)])
    def test_a_loader_resolves_its_file_list_in_one_call_a_chunk(
            self, cluster, monkeypatch, n, chunk):
        from alluxio_tpu.client import jax_io
        from alluxio_tpu.client.jax_io import DeviceBlockLoader

        monkeypatch.setattr(file_system_mod, "STATUS_BATCH_PATHS", chunk)
        monkeypatch.setattr(jax_io, "STATUS_BATCH_PATHS", chunk)
        fs = cluster.file_system()
        paths = [f"/job/shard-{i}" for i in range(n)]
        for i, p in enumerate(paths):
            fs.write_all(p, bytes([i]) * (BLOCK * (1 + i % 3)))
        plan, block_ids = _reference_plan(fs, paths)
        many, single, carried = (_served("get_status_many"),
                                 _served("get_status"),
                                 _count("Client.StatusBatchPaths"))
        set_tracing_enabled(True)
        tracer().clear()
        try:
            loader = DeviceBlockLoader(fs, paths)
            (span,) = tracer().snapshot(prefix="atpu.loader.resolve")
        finally:
            set_tracing_enabled(False)
            tracer().clear()
        try:
            calls = math.ceil(n / chunk)
            assert _served("get_status_many") - many == calls
            assert _served("get_status") == single
            assert _count("Client.StatusBatchPaths") - carried == n
            assert span["tags"] == {"paths": str(n), "calls": str(calls)}
            assert len(loader) == len(plan) and loader.plan == plan
            assert loader.block_ids_by_path == block_ids
            assert [b.shape[0] for b in loader.epoch()] == \
                [BLOCK] * len(plan)
        finally:
            loader.close()

    def test_a_loader_skips_the_paths_its_prefetch_service_resolved(
            self, cluster):
        from alluxio_tpu.client.jax_io import DeviceBlockLoader
        from alluxio_tpu.prefetch.oracle import DatasetManifest

        fs = cluster.file_system()
        paths = [f"/job/p{i}" for i in range(5)]
        for p in paths:
            fs.write_all(p, b"z" * BLOCK)
        many, single = _served("get_status_many"), _served("get_status")
        manifest = DatasetManifest.from_fs(fs, paths[:3])
        assert _served("get_status_many") == many + 1
        assert _served("get_status") == single
        assert [p for p, _ in manifest.file_infos] == paths[:3]
        assert [i for _, i in manifest.file_infos] == \
            [fs.get_status(p) for p in paths[:3]]
        assert [b.path for b in manifest.blocks] == paths[:3]
        service = types.SimpleNamespace(
            oracle=types.SimpleNamespace(manifest=manifest),
            bind_hbm=lambda fn: None)
        carried = _count("Client.StatusBatchPaths")
        loader = DeviceBlockLoader(fs, paths, prefetch_service=service)
        try:
            assert _count("Client.StatusBatchPaths") - carried == 2
            assert loader.plan == [(p, 0) for p in paths]
        finally:
            loader.close()

    def test_the_mesh_cache_resolves_block_ids_in_one_call(self, cluster):
        from alluxio_tpu.parallel.ici_store import MeshBlockCache

        fs = cluster.file_system()
        paths = [f"/job/m{i}" for i in range(4)]
        for p in paths:
            fs.write_all(p, b"m" * (2 * BLOCK))
        cache = MeshBlockCache.__new__(MeshBlockCache)
        cache._bids_by_path = {paths[0]: list(
            fs.get_status(paths[0]).block_ids)}
        cache.plan = [(p, i) for p in paths for i in (0, 1)] + \
            [(paths[1], 2)]
        many, single = _served("get_status_many"), _served("get_status")
        cache._resolve_block_ids(fs)
        assert _served("get_status_many") == many + 1
        assert _served("get_status") == single
        want = [fs.get_status(p).block_ids[i] for p in paths
                for i in (0, 1)] + [-1]
        assert cache.block_ids == want
