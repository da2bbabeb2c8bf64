"""Unix-socket metadata fast path (``rpc/fastpath.py``) + the deferred
group-commit journal contract it rides on (``journal/system.py``).

Reference behaviors being proven: same-host short-circuit transport
selection (``BlockInStream.java:80-124`` decision ladder, applied to
metadata), AsyncJournalWriter-style flush-before-respond
(``core/server/common/.../journal/AsyncJournalWriter.java``), and
chunked container-id reservation surviving replay
(``BlockContainerIdGenerator``)."""

import os
import tempfile
import threading
import time

import pytest

from alluxio_tpu.rpc.core import ServiceDefinition
from alluxio_tpu.rpc.fastpath import (
    FastPathChannel, FastPathNotSentError, FastPathServer, HybridChannel,
    is_local_host, socket_path_for,
)
from alluxio_tpu.utils.exceptions import (
    AlluxioTpuError, FileDoesNotExistError, UnavailableError,
)


@pytest.fixture()
def served(tmp_path):
    svc = ServiceDefinition("test.Svc")
    svc.unary("echo", lambda r: {"got": r})
    svc.unary("add", lambda r: {"sum": r["a"] + r["b"]})

    def boom(r):
        raise FileDoesNotExistError("/nope is gone")

    svc.unary("boom", boom)
    svc.stream_out("stream", lambda r: iter([{"x": 1}]))
    path = str(tmp_path / "fp.sock")
    server = FastPathServer(path)
    server.add_service(svc)
    server.start()
    yield path, server
    server.stop()


class TestFastPathServer:
    def test_unary_roundtrip(self, served):
        path, _ = served
        ch = FastPathChannel(path)
        assert ch.call("test.Svc", "add", {"a": 2, "b": 40})["sum"] == 42
        # persistent connection: many calls, one socket
        for i in range(50):
            assert ch.call("test.Svc", "echo", {"i": i})["got"]["i"] == i

    def test_typed_error_reraised(self, served):
        path, _ = served
        ch = FastPathChannel(path)
        with pytest.raises(FileDoesNotExistError, match="gone"):
            ch.call("test.Svc", "boom", {})

    def test_streaming_methods_not_served(self, served):
        path, _ = served
        ch = FastPathChannel(path)
        with pytest.raises(AlluxioTpuError, match="UNIMPLEMENTED|fastpath"):
            ch.call("test.Svc", "stream", {})

    def test_unknown_method(self, served):
        path, _ = served
        ch = FastPathChannel(path)
        with pytest.raises(AlluxioTpuError):
            ch.call("test.Svc", "nope", {})

    def test_server_stop_surfaces_unavailable(self, served):
        path, server = served
        ch = FastPathChannel(path)
        assert ch.call("test.Svc", "echo", {})["got"] == {}
        server.stop()
        with pytest.raises(UnavailableError):
            ch.call("test.Svc", "echo", {})

    def test_concurrent_threads_each_get_a_connection(self, served):
        path, _ = served
        ch = FastPathChannel(path)
        errs = []

        def worker(t):
            try:
                for i in range(30):
                    r = ch.call("test.Svc", "add", {"a": t, "b": i})
                    assert r["sum"] == t + i
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        ts = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errs


class _CountingGrpc:
    """Stands where the gRPC channel does behind a ``HybridChannel``."""

    def __init__(self, address: str) -> None:
        self.address, self.metadata, self.calls = address, (), []

    def call(self, service, method, request, timeout=30.0):
        self.calls.append(method)
        return {"route": "grpc"}


class TestNoResendAfterWrite:
    """``HybridChannel`` re-issues a call on gRPC only while the socket
    took nothing of it. Once the frame is written the handler may have
    run: a deadline or a lost connection surfaces as gRPC's would."""

    @pytest.fixture()
    def hybrid(self, tmp_path):
        ran = []
        server = FastPathServer(socket_path_for("localhost:7",
                                                str(tmp_path)))

        def slow(r):
            ran.append("slow")
            time.sleep(0.5)
            return {}

        def die(r):
            ran.append("die")
            server.stop()
            return {}

        svc = ServiceDefinition("test.Svc")
        svc.unary("echo", lambda r: {"route": "fastpath"})
        svc.unary("slow", slow)
        svc.unary("die", die)
        server.add_service(svc)
        server.start()
        grpc = _CountingGrpc("localhost:7")
        yield HybridChannel(grpc, fastpath_dir=str(tmp_path)), grpc, \
            ran, server
        server.stop()

    def test_a_deadline_after_the_write_is_not_reissued(self, hybrid):
        ch, grpc, ran, _server = hybrid
        with pytest.raises(AlluxioTpuError,
                           match="DEADLINE_EXCEEDED") as ei:
            ch.call("test.Svc", "slow", {}, timeout=0.1)
        assert not isinstance(ei.value, UnavailableError)
        assert ran == ["slow"] and grpc.calls == []
        # the socket is not given up for it: the next call reconnects
        assert ch.call("test.Svc", "echo", {}) == {"route": "fastpath"}
        assert grpc.calls == [] and not ch._fast_dead

    def test_a_connection_lost_after_the_write_is_not_reissued(
            self, hybrid):
        ch, grpc, ran, _server = hybrid
        assert ch.call("test.Svc", "echo", {}) == {"route": "fastpath"}
        with pytest.raises(UnavailableError) as ei:
            ch.call("test.Svc", "die", {})
        assert not isinstance(ei.value, FastPathNotSentError)
        assert ran == ["die"] and grpc.calls == []
        # nothing listens any more: THIS call was never sent, gRPC has it
        assert ch.call("test.Svc", "echo", {}) == {"route": "grpc"}
        assert grpc.calls == ["echo"] and ch._fast_dead

    @pytest.mark.parametrize("left", ["no-file", "stale-file"])
    def test_a_call_the_socket_never_took_falls_back(self, hybrid, left):
        ch, grpc, ran, server = hybrid
        if left == "no-file":
            server.stop()
        else:  # what a role killed with SIGKILL leaves behind
            server._server.shutdown()
            server._server.server_close()
            server._server = None
            assert os.path.exists(server._uds_path)
        assert ch.call("test.Svc", "echo", {}) == {"route": "grpc"}
        assert grpc.calls == ["echo"] and ran == []


class TestDiscovery:
    @pytest.mark.parametrize("env,directory,expected", [
        ({}, None, "{tmp}/atpu-master-19998.sock"),
        ({}, "", "{tmp}/atpu-master-19998.sock"),
        ({}, "/run", "/run/atpu-master-19998.sock"),
        ({"TMPDIR": "{own}"}, None, "{own}/atpu-master-19998.sock"),
        ({"ATPU_MASTER_FASTPATH_DIR": "/run/atpu", "TMPDIR": "{own}"},
         None, "/run/atpu/atpu-master-19998.sock"),
        ({"ATPU_MASTER_FASTPATH_DIR": "/run/atpu"}, "/srv",
         "/srv/atpu-master-19998.sock"),
        # sun_path holds 107 bytes: a longer path falls to /tmp
        ({}, "/" + "d" * 100, "/tmp/atpu-master-19998.sock"),
    ], ids=["default", "empty-conf", "conf", "TMPDIR", "env-override",
            "conf-over-env", "too-long"])
    def test_socket_path_convention(self, tmp_path, monkeypatch, env,
                                    directory, expected):
        """One decision for servers and clients: the conf's directory,
        else the env override, else the process's temp directory."""
        monkeypatch.delenv("ATPU_MASTER_FASTPATH_DIR", raising=False)
        monkeypatch.delenv("TMPDIR", raising=False)
        tempfile.tempdir = None  # gettempdir() caches its answer
        own = str(tmp_path)
        for k, v in env.items():
            monkeypatch.setenv(k, v.format(own=own))
        try:
            tmp = "/tmp" if "TMPDIR" in env else tempfile.gettempdir()
            assert socket_path_for("localhost:19998", directory) == \
                expected.format(tmp=tmp, own=own)
        finally:
            tempfile.tempdir = None

    def test_is_local_host(self):
        assert is_local_host("localhost")
        assert is_local_host("127.0.0.1")
        assert not is_local_host("some-remote-box.example.com")


class TestClusterFastPath:
    def test_local_cluster_clients_ride_fastpath(self, tmp_path):
        """The LocalCluster master serves the socket; the FileSystem
        client's hybrid channel actually uses it (verified by breaking
        gRPC-only assumptions: we count fastpath connections)."""
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        with LocalCluster(str(tmp_path), num_workers=1) as c:
            sock = socket_path_for(f"localhost:{c.master.rpc_port}")
            assert os.path.exists(sock)
            fs = c.file_system()
            fs.write_all("/fp/x", b"abc")
            assert fs.read_all("/fp/x") == b"abc"
            infos = fs.list_status("/fp")
            assert [i.name for i in infos] == ["x"]
            ch = fs.fs_master._channels[0]
            assert ch._fast is not None and not ch._fast_dead

    def test_fastpath_disabled_still_works(self, tmp_path, monkeypatch):
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        monkeypatch.setenv("ATPU_FASTPATH_DISABLE", "1")
        with LocalCluster(str(tmp_path), num_workers=1) as c:
            fs = c.file_system()
            fs.write_all("/g/x", b"grpc-only")
            assert fs.read_all("/g/x") == b"grpc-only"

    def test_fallback_to_grpc_when_socket_dies(self, tmp_path):
        """Killing only the fastpath server must not break clients —
        the hybrid channel falls back to gRPC transparently."""
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        with LocalCluster(str(tmp_path), num_workers=1) as c:
            fs = c.file_system()
            fs.write_all("/fb/x", b"1")
            c.master.fastpath_server.stop()
            c.master.fastpath_server = None
            assert fs.read_all("/fb/x") == b"1"  # still answered (gRPC)
            assert fs.exists("/fb/x")


class TestConcurrentMutations:
    def test_creates_and_block_commits_interleave(self, tmp_path):
        """Regression for the container-id-reservation ABBA deadlock:
        create_file (reservation journal write) racing commit_block
        (journal apply -> BlockMaster._lock) must make progress. Data
        writes exercise BOTH paths on every file."""
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        with LocalCluster(str(tmp_path), num_workers=1) as c:
            fs = c.file_system()
            errs = []

            def writer(t):
                try:
                    for i in range(25):
                        fs.write_all(f"/cc/{t}-{i}", b"x" * 128)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            ts = [threading.Thread(target=writer, args=(t,))
                  for t in range(4)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in ts), \
                "writers wedged (journal/lock ordering deadlock?)"
            assert not errs, errs
            assert len(fs.list_status("/cc")) == 100

    def test_reservation_does_not_burn_chunks(self, tmp_path):
        """Live self-apply must not advance the generator: 50 creates
        should consume ~50 container ids, not 50 x CHUNK."""
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        with LocalCluster(str(tmp_path), num_workers=1) as c:
            fs = c.file_system()
            for i in range(50):
                fs.write_all(f"/burn/f-{i}", b"")
            bm = c.master.block_master
            assert bm.container_ids.peek < 200, \
                f"generator burned to {bm.container_ids.peek}"


class TestDurabilityContract:
    def test_acknowledged_creates_survive_replay(self, tmp_path):
        """Deferred group commit must still mean: acknowledged => in the
        journal. Every file whose create RPC returned must exist after
        a full journal replay (fresh master over the same folder)."""
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        base = str(tmp_path)
        with LocalCluster(base, num_workers=1) as c:
            fs = c.file_system()
            for i in range(120):
                fs.write_all(f"/d/f-{i}", b"")
        with LocalCluster(base, num_workers=1) as c:
            fs = c.file_system()
            names = {i.name for i in fs.list_status("/d")}
            assert names == {f"f-{i}" for i in range(120)}

    def test_container_ids_never_reissued_after_replay(self, tmp_path):
        """Chunked id reservation: replay must resume ABOVE every id
        handed out before the restart, even though only the high-water
        mark was journaled."""
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        base = str(tmp_path)
        with LocalCluster(base, num_workers=1) as c:
            fs = c.file_system()
            for i in range(10):
                fs.write_all(f"/ids/a-{i}", b"")
            ids1 = {i.file_id for i in fs.list_status("/ids")}
        with LocalCluster(base, num_workers=1) as c:
            fs = c.file_system()
            for i in range(10):
                fs.write_all(f"/ids/b-{i}", b"")
            ids2 = {i.file_id for i in fs.list_status("/ids")}
            assert len(ids2) == 20  # no collisions
            assert ids1 < ids2


class TestJournalDeferredScope:
    def test_deferred_scope_flushes_on_exit(self, tmp_path):
        from alluxio_tpu.journal.system import LocalJournalSystem

        class KV:
            journal_name = "kv"

            def __init__(self):
                self.data = {}

            def process_entry(self, e):
                if e.type != "kv_put":
                    return False
                self.data[e.payload["k"]] = e.payload["v"]
                return True

            def snapshot(self):
                return dict(self.data)

            def restore(self, s):
                self.data = dict(s)

            def reset_state(self):
                self.data = {}

        j = LocalJournalSystem(str(tmp_path / "j"))
        kv = KV()
        j.register(kv)
        j.start()
        j.gain_primacy()
        with j.deferred_durability():
            with j.create_context() as ctx:
                ctx.append("kv_put", {"k": "a", "v": 1})
            with j.create_context() as ctx:
                ctx.append("kv_put", {"k": "b", "v": 2})
            # applied immediately...
            assert kv.data == {"a": 1, "b": 2}
            # ...but not necessarily durable inside the scope
        # after scope exit: durable (every accepted write ticket synced)
        assert j._write_ticket >= 2
        assert j._synced_ticket >= j._write_ticket
        j.stop()

        j2 = LocalJournalSystem(str(tmp_path / "j"))
        kv2 = KV()
        j2.register(kv2)
        j2.start()
        j2.gain_primacy()
        assert kv2.data == {"a": 1, "b": 2}
        j2.stop()
