"""The worker's same-host Unix-socket fast path (``rpc/worker_service.
serve_worker`` + ``rpc/fastpath.py``): the lease plane's ``shm_open`` /
``shm_release`` / ``shm_renew`` and every other unary worker call ride
the socket the master's kind of client already probes, count themselves
as ``Worker.RpcServed.<route>.<method>``, cross it with their typed
errors, and fall back to gRPC exactly as a master client does. One read
through ``DeviceBlockLoader`` is held to the bytes written on every
route: the socket, a worker whose socket is gone (``stop_fastpath()``)
and a client told to stay off it (``ATPU_FASTPATH_DISABLE``). A worker
always serves the socket: there is no switch for it."""

import glob
import os
import time

import numpy as np
import pytest

from alluxio_tpu.conf import Keys
from alluxio_tpu.metrics import metrics
from alluxio_tpu.minicluster import LocalCluster
from alluxio_tpu.rpc.clients import WorkerClient
from alluxio_tpu.rpc.fastpath import FastPathChannel, socket_path_for
from alluxio_tpu.rpc.worker_service import WORKER_SERVICE
from alluxio_tpu.shm import ShmLeaseDeniedError, ShmSegmentUnavailableError
from alluxio_tpu.utils import faults
from alluxio_tpu.utils.exceptions import (
    AlluxioTpuError, ResourceExhaustedError, UnavailableError,
)

BLOCK = 64 << 10
#: a route = (environment of the client, whether the worker's socket
#: is taken away before any client is made)
ROUTES = {
    "fastpath": ({}, False),
    "grpc-no-socket": ({}, True),
    "grpc-client-env": ({"ATPU_FASTPATH_DISABLE": "1"}, False),
}


def _served(route: str, method: str) -> int:
    return metrics().counter(f"Worker.RpcServed.{route}.{method}").count


def _taken(route_id: str) -> tuple:
    """(the dispatcher a route's calls must land on, the other one)."""
    return ("fastpath", "grpc") if route_id == "fastpath" \
        else ("grpc", "fastpath")


def _pattern(n_blocks: int, seed: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n_blocks * BLOCK, dtype=np.uint8).tobytes()


@pytest.fixture(params=sorted(ROUTES))
def routed(request, tmp_path, monkeypatch):
    """``(route id, cluster)``; the segment cache holds 2 mappings, so a
    scan of more blocks gives the oldest back with ``shm_release``."""
    env, no_socket = ROUTES[request.param]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    with LocalCluster(str(tmp_path), num_workers=1, block_size=BLOCK,
                      conf_overrides={
                          Keys.USER_SHM_SEGMENT_CACHE_MAX: 2}) as c:
        if no_socket:
            c.workers[0].server.stop_fastpath()
        yield request.param, c


@pytest.fixture()
def cluster(tmp_path):
    with LocalCluster(str(tmp_path), num_workers=1,
                      block_size=BLOCK) as c:
        yield c


@pytest.fixture()
def clean_faults():
    faults.injector().reset()
    yield faults.injector()
    faults.injector().reset()


def _loader_bytes(fs, path: str) -> bytes:
    from alluxio_tpu.client.jax_io import DeviceBlockLoader

    loader = DeviceBlockLoader(fs, [path])
    try:
        return b"".join(np.asarray(a).tobytes() for a in loader.epoch())
    finally:
        loader.close()


class TestLeasePlaneRoute:
    def test_leases_ride_the_route_and_the_loader_reads_back(self, routed):
        route_id, c = routed
        took, other = _taken(route_id)
        fs = c.file_system()
        data = _pattern(6, 30)
        fs.write_all("/lease/scan.bin", data, write_type="MUST_CACHE")
        before = {(r, m): _served(r, m) for r in (took, other)
                  for m in ("shm_open", "shm_release")}
        assert _loader_bytes(fs, "/lease/scan.bin") == data
        assert fs.store.shm.drain(10.0)  # the releases run on its thread
        # a lease a block, a release for each mapping past the cache's 2
        assert _served(took, "shm_open") - before[took, "shm_open"] == 6
        assert _served(took, "shm_release") - \
            before[took, "shm_release"] >= 4
        for m in ("shm_open", "shm_release"):
            assert _served(other, m) == before[other, m]
        fs.close()

    def test_a_route_never_taken_reads_zero_not_absent(self, routed):
        _route_id, c = routed
        snap = WorkerClient(c.workers[0].address).get_metrics()
        for route in ("grpc", "fastpath"):
            for m in ("shm_open", "shm_release", "shm_renew"):
                assert f"Worker.RpcServed.{route}.{m}" in snap

    def test_the_short_circuit_write_rides_the_route_too(self, routed):
        route_id, c = routed
        took, other = _taken(route_id)
        before = {r: _served(r, "complete_local_block")
                  for r in (took, other)}
        fs = c.file_system()
        fs.write_all("/lease/w.bin", b"w" * BLOCK, write_type="MUST_CACHE")
        assert _served(took, "complete_local_block") - before[took] == 1
        assert _served(other, "complete_local_block") == before[other]
        fs.close()


class TestTypedErrorsAndFallbacks:
    @pytest.mark.parametrize("error", ["denied", "unavailable"])
    def test_typed_lease_errors_cross_the_route_and_the_ladder_falls(
            self, routed, clean_faults, monkeypatch, error):
        route_id, c = routed
        took, _other = _taken(route_id)
        fs = c.file_system()
        data = _pattern(1, 31)
        fs.write_all("/lease/typed.bin", data, write_type="MUST_CACHE")
        block_id = fs.get_status("/lease/typed.bin").block_ids[0]
        if error == "denied":
            clean_faults.set(shm_lease_deny_rate=1.0)
            expected = ShmLeaseDeniedError
        else:
            def gone(_session, bid):
                raise ShmSegmentUnavailableError(f"block {bid} mid-move")

            monkeypatch.setattr(c.workers[0].worker.shm_store, "open",
                                gone)
            expected = ShmSegmentUnavailableError
        before = _served(took, "shm_open")
        with pytest.raises(expected) as ei:
            fs.store.worker_client(
                c.workers[0].worker.address).shm_open(1, block_id)
        assert type(ei.value) is expected
        assert _served(took, "shm_open") - before == 1
        # the read ladder catches exactly these: next rung, same bytes
        with fs.open_file("/lease/typed.bin") as f:
            bs = f.block_stream(0)
            assert type(bs).__name__ != "ShmBlockInStream"
            assert bs.pread(0, BLOCK) == data
        fs.close()

    def test_an_injected_rpc_reject_reaches_a_worker_call(
            self, routed, clean_faults):
        """The chaos hook sits in ``check_admission``, which both
        dispatchers share: riding the socket is no way around it."""
        route_id, c = routed
        took, _other = _taken(route_id)
        worker = WorkerClient(c.workers[0].address, retry_duration_s=0.0)
        assert worker.get_metrics()  # connected, on its route
        clean_faults.set(rpc_reject_rate=1.0, scope="shm_renew")
        before = _served(took, "shm_renew")
        with pytest.raises(ResourceExhaustedError) as ei:
            worker.shm_renew(1, 1)
        assert ei.value.retry_after_s > 0
        assert clean_faults.injected["rpc_reject"] == 1
        assert _served(took, "shm_renew") - before == 1

    def test_socket_gone_mid_run_falls_back_to_grpc(self, cluster):
        fs = cluster.file_system()
        data = _pattern(4, 32)
        fs.write_all("/lease/a.bin", data[:2 * BLOCK],
                     write_type="MUST_CACHE")
        fs.write_all("/lease/b.bin", data[2 * BLOCK:],
                     write_type="MUST_CACHE")
        fast0, grpc0 = _served("fastpath", "shm_open"), \
            _served("grpc", "shm_open")
        assert _loader_bytes(fs, "/lease/a.bin") == data[:2 * BLOCK]
        assert _served("fastpath", "shm_open") - fast0 == 2
        endpoint = cluster.workers[0].server
        sock = socket_path_for(cluster.workers[0].address)
        assert os.path.exists(sock)
        endpoint.stop_fastpath()
        assert not os.path.exists(sock)
        assert _loader_bytes(fs, "/lease/b.bin") == data[2 * BLOCK:]
        assert _served("fastpath", "shm_open") - fast0 == 2
        assert _served("grpc", "shm_open") - grpc0 == 2
        fs.close()

    def test_a_slow_lease_is_not_run_a_second_time_on_grpc(
            self, cluster, monkeypatch):
        """A deadline that passes AFTER the request was written leaves a
        call that may have run: it surfaces as a deadline, as on gRPC,
        and is not re-issued there (a second ``shm_open`` would be a
        second pin); the client stays on the socket."""
        fs = cluster.file_system()
        fs.write_all("/lease/slow.bin", _pattern(1, 35),
                     write_type="MUST_CACHE")
        block_id = fs.get_status("/lease/slow.bin").block_ids[0]
        store = cluster.workers[0].worker.shm_store
        real_open, opened = store.open, []

        def slow_open(session, bid):
            opened.append(bid)
            time.sleep(0.6)
            return real_open(session, bid)

        monkeypatch.setattr(store, "open", slow_open)
        worker = fs.store.worker_client(cluster.workers[0].worker.address)
        fast0, grpc0 = _served("fastpath", "shm_open"), \
            _served("grpc", "shm_open")
        with pytest.raises(AlluxioTpuError, match="DEADLINE_EXCEEDED") \
                as ei:
            worker._channel.call(WORKER_SERVICE, "shm_open",
                                 {"session_id": 1, "block_id": block_id},
                                 timeout=0.1)
        assert not isinstance(ei.value, UnavailableError)
        deadline = time.monotonic() + 10
        while _served("fastpath", "shm_open") - fast0 < 1 and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        assert opened == [block_id]
        assert _served("grpc", "shm_open") == grpc0
        monkeypatch.setattr(store, "open", real_open)
        lease = worker.shm_open(1, block_id)
        worker.shm_release(1, lease["lease_id"])
        assert _served("fastpath", "shm_open") - fast0 == 2
        assert _served("grpc", "shm_open") == grpc0
        fs.close()


class TestServing:
    def test_sockets_are_unlinked_on_stop(self, tmp_path):
        socks = str(tmp_path / "socks")
        os.makedirs(socks)
        with LocalCluster(str(tmp_path / "c"), num_workers=2,
                          conf_overrides={
                              Keys.MASTER_FASTPATH_DIR: socks}) as c:
            want = {socket_path_for(a, socks) for a in
                    (c.master.address, c.workers[0].address,
                     c.workers[1].address)}
            assert set(glob.glob(f"{socks}/atpu-master-*.sock")) == want
        assert os.listdir(socks) == []

    def test_the_conf_directory_reaches_the_worker_client(self, tmp_path):
        """A ``FileSystem`` whose conf names another directory probes
        THAT directory for a worker's socket, as for the master's."""
        socks = str(tmp_path / "socks")
        os.makedirs(socks)
        with LocalCluster(str(tmp_path / "c"), num_workers=1,
                          block_size=BLOCK, conf_overrides={
                              Keys.MASTER_FASTPATH_DIR: socks}) as c:
            fs = c.file_system()
            data = _pattern(2, 33)
            fs.write_all("/lease/dir.bin", data, write_type="MUST_CACHE")
            fast0, grpc0 = _served("fastpath", "shm_open"), \
                _served("grpc", "shm_open")
            assert _loader_bytes(fs, "/lease/dir.bin") == data
            assert _served("fastpath", "shm_open") - fast0 == 2
            assert _served("grpc", "shm_open") == grpc0
            fs.close()

    def test_an_unclaimable_socket_leaves_the_worker_on_grpc(
            self, tmp_path):
        nowhere = str(tmp_path / "no" / "such" / "dir")
        with LocalCluster(str(tmp_path / "c"), num_workers=1,
                          block_size=BLOCK, conf_overrides={
                              Keys.MASTER_FASTPATH_DIR: nowhere}) as c:
            assert c.workers[0].server.fastpath is None
            assert c.master.fastpath_server is None
            fs = c.file_system()
            data = _pattern(2, 34)
            fs.write_all("/lease/nosock.bin", data,
                         write_type="MUST_CACHE")
            grpc0 = _served("grpc", "shm_open")
            assert _loader_bytes(fs, "/lease/nosock.bin") == data
            assert _served("grpc", "shm_open") - grpc0 == 2
            fs.close()

    @pytest.mark.parametrize("method", ["read_block", "write_block"])
    def test_streaming_methods_are_not_served_on_the_socket(
            self, cluster, method):
        ch = FastPathChannel(socket_path_for(cluster.workers[0].address))
        with pytest.raises(AlluxioTpuError, match="no fastpath handler"):
            ch.call(WORKER_SERVICE, method, {"block_id": 1})
        ch.close_thread_connection()

    def test_every_unary_method_is_served_on_the_socket(self, cluster):
        endpoint = cluster.workers[0].server
        (svc,) = endpoint.server.services()
        unary = {m for m, (_fn, kind) in svc.methods.items()
                 if kind == "unary"}
        assert {"shm_open", "shm_renew", "shm_release", "read_many",
                "complete_local_block", "get_metrics"} <= unary
        assert set(endpoint.fastpath._methods) == \
            {(WORKER_SERVICE, m) for m in unary}

    def test_the_socket_authenticates_with_the_workers_authenticator(
            self, tmp_path):
        """A QoS worker authenticates every gRPC call; the socket's
        hello frame is held to the same authenticator, and the handler
        sees the same principal."""
        from alluxio_tpu.utils.exceptions import UnauthenticatedError

        with LocalCluster(str(tmp_path), num_workers=1,
                          conf_overrides={
                              Keys.WORKER_QOS_ENABLED: True}) as c:
            endpoint = c.workers[0].server
            assert endpoint.fastpath._auth is not None
            sock = socket_path_for(c.workers[0].address)
            nobody = FastPathChannel(sock, metadata=())
            with pytest.raises(UnauthenticatedError):
                nobody.call(WORKER_SERVICE, "session_heartbeat", {})
            seen = []
            fn, timer = endpoint.fastpath._methods[
                (WORKER_SERVICE, "session_heartbeat")]

            def spy(req):
                from alluxio_tpu.security.user import authenticated_user

                seen.append(authenticated_user().name)
                return fn(req)

            endpoint.fastpath._methods[
                (WORKER_SERVICE, "session_heartbeat")] = (spy, timer)
            someone = FastPathChannel(
                sock, metadata=(("atpu-user", "tenant-a"),))
            assert someone.call(
                WORKER_SERVICE, "session_heartbeat", {}) == {}
            assert seen == ["tenant-a"]
            someone.close_thread_connection()


class TestServedCounters:
    @pytest.mark.parametrize("fastpath", [True, False])
    def test_master_calls_count_their_route(self, cluster, fastpath):
        from alluxio_tpu.rpc.clients import FsMasterClient

        took, other = ("fastpath", "grpc") if fastpath \
            else ("grpc", "fastpath")

        def served(route):
            return metrics().counter(
                f"Master.RpcServed.{route}.exists").count

        before = {r: served(r) for r in (took, other)}
        client = FsMasterClient(cluster.master.address, fastpath=fastpath)
        assert client.exists("/") is True
        assert served(took) - before[took] == 1
        assert served(other) == before[other]

    @pytest.mark.parametrize("fastpath", [True, False])
    def test_a_traced_server_span_carries_the_admission_phase(
            self, cluster, fastpath):
        """Ring on, both dispatchers lay the same span with the same
        phases: the socket's is not a poorer record than gRPC's."""
        from alluxio_tpu.utils.tracing import set_tracing_enabled, tracer

        worker = WorkerClient(cluster.workers[0].address, fastpath=fastpath)
        set_tracing_enabled(True)
        tracer().clear()
        try:
            worker._call("session_heartbeat", {})
            (span,) = tracer().snapshot(
                prefix=f"{WORKER_SERVICE}.session_heartbeat")
        finally:
            set_tracing_enabled(False)
            tracer().clear()
        assert "admission" in dict(map(tuple, span["phases"]))

    def test_the_grpc_wrapper_is_built_once_a_method(self):
        """grpc asks the generic handler for a method's handler on
        every call; the wrapper (and its serve timer) is made once, and
        again only for a handler swapped in place (the HA fence)."""
        from alluxio_tpu.rpc.core import ServiceDefinition, _GenericHandler

        class _Details:
            method = "/test.Svc/echo"

        svc = ServiceDefinition("test.Svc")
        svc.unary("echo", lambda r: r)
        generic = _GenericHandler({"test.Svc": svc})
        first = generic.service(_Details())
        assert generic.service(_Details()) is first
        svc.methods["echo"] = (lambda r: {"fenced": r}, "unary")
        assert generic.service(_Details()) is not first
