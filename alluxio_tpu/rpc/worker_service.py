"""Worker data-server RPC service.

Re-design of ``core/server/worker/.../grpc/{GrpcDataServer.java:50,
BlockReadHandler.java:59,BlockWriteHandler,ShortCircuitBlockReadHandler,
ShortCircuitBlockWriteHandler}.java`` + ``grpc/block_worker.proto:13-29``:

- ``read_block``: server-stream of chunks; cold blocks fall back to UFS
  read-through when the request carries a UFS descriptor. gRPC's own HTTP/2
  flow control replaces the reference's hand-rolled ``offset_received``
  receipts.
- ``write_block``: client-stream (header, chunks..., commit) -> length.
- ``shm_open`` / ``shm_renew`` / ``shm_release``: the same-host lease
  plane; a lease is a TTL pin on the block's file in whatever tier holds
  it (``worker/shm_store.py``), which the client mmaps.
- ``create_local_block`` / ``complete_local_block``: the short-circuit
  write of a same-host client.
- ``async_cache``, ``remove_block``, ``move_block``: unary control ops.
"""

from __future__ import annotations

from typing import Iterator

from alluxio_tpu.rpc.core import (
    RpcServer, ServiceDefinition, register_served,
)
from alluxio_tpu.utils.exceptions import (
    BlockDoesNotExistError, InvalidArgumentError, best_effort,
)
from alluxio_tpu.worker.process import BlockWorker
from alluxio_tpu.worker.ufs_io import UfsBlockDescriptor

WORKER_SERVICE = "atpu.BlockWorker"

DEFAULT_CHUNK = 1 << 20
#: Worker.ReadBlockTime (per-MiB warm produce time, feeds the
#: read-latency-p99-regression health rule) is only sampled for reads
#: of at least this many bytes served in chunks of at least this size:
#: below either bound, the fixed per-read-call cost dominates the
#: normalized figure and bills a client's configuration to the host
P99_SAMPLE_MIN_BYTES = 1 << 18
P99_SAMPLE_MIN_CHUNK = 1 << 16


def _principal() -> str:
    """The authenticated caller's name, for per-tenant QoS accounting;
    empty (one anonymous tenant) when the worker runs no authenticator
    (QoS disabled) or the call is in-process."""
    from alluxio_tpu.security.user import authenticated_user

    user = authenticated_user()
    return user.name if user is not None else ""


def worker_service(worker: BlockWorker) -> ServiceDefinition:
    svc = ServiceDefinition(WORKER_SERVICE)

    # ---------------------------------------------------------- read stream
    def read_block(req: dict) -> Iterator[dict]:
        """Chunks carry ``source`` — the serving tier alias (MEM/SSD/...)
        or ``UFS`` for a cold read-through — so clients can attribute
        every byte to the tier that produced it (input doctor).
        Warm serving speed is timed into ``Worker.ReadBlockTime``: its
        per-worker ``.p99`` rides the metrics heartbeat and is what the
        master's read-latency-regression health rule compares against
        the fleet median, so the sample must isolate *this host's*
        serving speed — only the tier ``r.read`` calls are timed (per-
        chunk RPC framing is excluded, or a client's small-chunk config
        would inflate this host's number), one sample per stream
        normalized to seconds-per-MiB, excluding yield suspension (the
        client paces its own drain), the post-last-chunk cache-fill
        commit wait, and UFS-sourced chunks (cold read-through latency
        is the UFS's, tracked by ``Worker.UfsFetch*``).  One generator,
        no wrapper: stream cancel (hedged remote reads cancel losers
        routinely) closes it directly, the ``with`` releases the block
        reader's eviction pin NOW, and the ``finally`` still records
        the partial progress."""
        import time as _time

        from alluxio_tpu.metrics import metrics
        from alluxio_tpu.utils import faults
        from alluxio_tpu.utils.tracing import current_span

        clock = _time.monotonic
        fault_host = worker.address.tiered_identity.value("host") \
            or worker.address.host
        block_id = req["block_id"]
        offset = req.get("offset", 0)
        length = req.get("length", -1)
        # clamp: chunk_size<=0 from a buggy client would spin the
        # cached-tier loop forever without advancing pos
        chunk = max(1, req.get("chunk_size", DEFAULT_CHUNK))
        m = metrics()
        # the server span (opened by the RPC wrapper) stays live across
        # the generator's resumptions on this thread; phase timings are
        # accumulated locally and emitted ONCE at stream end
        sp = current_span()
        if worker.store.has_block(block_id):
            produce_s = 0.0
            produced_b = 0
            wire_s = 0.0
            try:
                # open_reader emits the ``lock_wait`` phase itself
                # (tiered_store.get_reader times the block-lock acquire)
                with worker.open_reader(block_id) as r:
                    tier = r.tier_alias or "MEM"
                    m.counter(f"Worker.BlocksServed.{tier}").inc()
                    served = m.counter(f"Worker.BytesServed.{tier}")
                    end = r.length if length < 0 \
                        else min(r.length, offset + length)
                    pos = offset
                    while pos < end:  # the reference's hot loop
                        n = min(chunk, end - pos)
                        t0 = clock()
                        data = r.read(pos, n)
                        if faults.armed():
                            # inside the timed region on purpose: the
                            # injected straggler must show up in
                            # Worker.ReadBlockTime (and thus in the
                            # p99-regression rule) like a real one
                            faults.injector().maybe_sleep_read(
                                fault_host)
                        produce_s += clock() - t0
                        produced_b += len(data)
                        if sp is None:
                            yield {"data": data, "offset": pos,
                                   "source": tier}
                        else:
                            # yield suspension = grpc serialize + send
                            # + HTTP/2 flow control: the per-op RPC
                            # overhead the microscope exists to expose
                            t_y = clock()
                            yield {"data": data, "offset": pos,
                                   "source": tier}
                            wire_s += clock() - t_y
                        served.inc(n)
                        pos += n
            finally:
                if sp is not None:
                    sp.phase("tier_read", produce_s * 1000.0)
                    sp.phase("wire", wire_s * 1000.0)
                # sample only reads whose per-MiB figure the fixed
                # per-read-call overhead cannot skew: a client-chosen
                # tiny chunk size multiplies that fixed cost into
                # ms/MiB (1 KiB chunks = 1024 calls/MiB), and a tiny
                # read scales one call's cost by up to 2^20/bytes —
                # either would false-fire the p99 fleet-regression
                # rule against a healthy host
                if produced_b >= P99_SAMPLE_MIN_BYTES and \
                        chunk >= P99_SAMPLE_MIN_CHUNK:
                    m.timer("Worker.ReadBlockTime").update(
                        produce_s * ((1 << 20) / produced_b))
            return
        ufs = req.get("ufs")
        if not ufs:
            raise BlockDoesNotExistError(
                f"block {block_id} not cached and no UFS fallback given")
        desc = UfsBlockDescriptor(
            block_id=block_id, ufs_path=ufs["ufs_path"],
            offset=ufs["offset"], length=ufs["length"],
            mount_id=ufs.get("mount_id", 0))
        # streaming read-through: chunks go out as stripes land, so the
        # client's first byte costs one stripe, not the whole block; the
        # tiered-store fill proceeds in parallel inside the fetch.
        # A blocked reader is ON_DEMAND — it overtakes (and, when
        # coalescing, promotes) queued background fills — and carries
        # the caller's principal for the per-tenant stripe caps
        fetch = worker.open_ufs_fetch(desc, cache=req.get("cache", True),
                                      tenant=_principal())
        m.counter("Worker.BlocksServed.UFS").inc()
        served = m.counter("Worker.BytesServed.UFS")
        end = desc.length if length < 0 else min(desc.length,
                                                 offset + length)
        pos = offset
        wire_s = 0.0
        for data in fetch.iter_range(offset, max(0, end - offset),
                                     chunk_size=chunk):
            if sp is None:
                yield {"data": data, "offset": pos, "source": "UFS"}
            else:
                t_y = clock()
                yield {"data": data, "offset": pos, "source": "UFS"}
                wire_s += clock() - t_y
            served.inc(len(data))
            pos += len(data)
        if sp is not None:
            sp.phase("wire", wire_s * 1000.0)
        # the cache-fill commit trails the last stripe; close the
        # stream only once it lands so "read completed" keeps implying
        # "block cached" for clients and heartbeats (seed semantics).
        # A fetch that FAILED after serving this sub-range fails the
        # stream too (the old whole-block path failed such reads); a
        # slow commit alone (timeout, error is None) stays best-effort
        if not fetch.wait_done(30.0) and fetch.error is not None:
            raise fetch.error if isinstance(fetch.error, Exception) \
                else IOError(str(fetch.error))

    svc.stream_out("read_block", read_block)

    # -------------------------------------------------- scatter/gather read
    def read_many(req: dict) -> dict:
        """Batch of small reads against ONE block, served in one RPC:
        ``{block_id, offsets: [..], sizes: [..]}`` -> one concatenated
        payload + per-op lengths. One reader open, one block lock, one
        serialization — the per-op RPC cost the random-4k drill showed
        dominating (``wire`` ~85% of self-time) is paid once per batch
        instead of once per read. Ops are served in request order; a
        short read (op past EOF) yields a short slice, matching what
        the same per-op ``read_block`` calls would return."""
        import time as _time

        from alluxio_tpu.metrics import metrics
        from alluxio_tpu.utils.tracing import current_span

        block_id = req["block_id"]
        offsets = req["offsets"]
        sizes = req["sizes"]
        if len(offsets) != len(sizes):
            raise InvalidArgumentError(
                f"read_many: {len(offsets)} offsets vs {len(sizes)} sizes")
        m = metrics()
        sp = current_span()
        t0 = _time.perf_counter()
        lengths = []
        parts = []
        with worker.open_reader(block_id) as r:
            tier = r.tier_alias or "MEM"
            served = m.counter(f"Worker.BytesServed.{tier}")
            for off, size in zip(offsets, sizes):
                data = r.read(off, max(0, size))
                parts.append(data)
                lengths.append(len(data))
                served.inc(len(data))
        m.counter(f"Worker.BlocksServed.{tier}").inc()
        m.counter("Worker.BatchReadOps").inc(len(offsets))
        if sp is not None:
            # the whole gather is one tier_read burst; batch_read is the
            # assembly slice the critical-path analyzer attributes to
            # this subsystem
            sp.phase("batch_read", (_time.perf_counter() - t0) * 1000.0)
        return {"data": b"".join(parts), "lengths": lengths,
                "source": tier}

    svc.unary("read_many", read_many)

    # ------------------------------------------------------ shm lease plane
    def shm_open(req: dict) -> dict:
        return worker.shm_store.open(req["session_id"], req["block_id"])

    def shm_renew(req: dict) -> dict:
        return worker.shm_store.renew(req["session_id"], req["lease_id"])

    def shm_release(req: dict) -> dict:
        return {"released": worker.shm_store.release(
            req["session_id"], req["lease_id"])}

    svc.unary("shm_open", shm_open)
    svc.unary("shm_renew", shm_renew)
    svc.unary("shm_release", shm_release)
    # which route a same-host client's leases took is read from a
    # pull of these: one never taken must read 0, not "absent"
    register_served(WORKER_SERVICE, ("shm_open", "shm_renew",
                                     "shm_release"))

    # ---------------------------------------------------------- write stream
    def write_block(requests: Iterator[dict]) -> dict:
        header = next(requests)
        block_id = header["block_id"]
        session_id = header["session_id"]
        tier = header.get("tier", "")
        worker.create_block(session_id, block_id,
                            initial_bytes=header.get("size_hint", DEFAULT_CHUNK),
                            tier_alias=tier)
        length = 0
        try:
            with worker.get_temp_writer(session_id, block_id) as w:
                for msg in requests:
                    if msg.get("cancel"):
                        raise InvalidArgumentError("write cancelled")
                    data = msg.get("data")
                    if data:
                        w.append(data)
                        length += len(data)
            worker.commit_block(session_id, block_id,
                                pinned=header.get("pinned", False))
        except BaseException:
            best_effort("write abort", worker.abort_block,
                        session_id, block_id)
            raise
        return {"length": length}

    svc.stream_in("write_block", write_block)

    # ------------------------------------------------------- short circuit
    def create_local_block(req: dict) -> dict:
        path = worker.create_block(
            req["session_id"], req["block_id"],
            initial_bytes=req.get("size_hint", DEFAULT_CHUNK),
            tier_alias=req.get("tier", ""))
        return {"path": path}

    def complete_local_block(req: dict) -> dict:
        if req.get("cancel"):
            worker.abort_block(req["session_id"], req["block_id"])
        else:
            worker.commit_block(req["session_id"], req["block_id"],
                                pinned=req.get("pinned", False))
        return {}

    svc.unary("create_local_block", create_local_block)
    svc.unary("complete_local_block", complete_local_block)

    # -------------------------------------------------------------- control
    def async_cache(r: dict) -> dict:
        """``qos_class`` (optional wire string, default ASYNC_FILL)
        lets the prefetch agent tag its speculative loads PREFETCH so
        they drain after client-issued fills and on-demand reads."""
        from alluxio_tpu.qos import priority_from_name

        return {"accepted": worker.async_cache.submit(
            UfsBlockDescriptor(
                block_id=r["block_id"], ufs_path=r["ufs_path"],
                offset=r["offset"], length=r["length"],
                mount_id=r.get("mount_id", 0)),
            priority=priority_from_name(r.get("qos_class", "")),
            tenant=_principal())}

    svc.unary("async_cache", async_cache)
    svc.unary("prefetch_pin", lambda r: {
        "pinned": worker.store.pin_prefetch(r["block_id"],
                                            r.get("ttl_s", 600.0))})
    svc.unary("prefetch_unpin", lambda r: (
        worker.store.unpin_prefetch(r["block_id"]), {})[-1])
    svc.unary("remove_block", lambda r: (
        worker.store.remove_block(r["block_id"]), {})[-1])
    svc.unary("move_block", lambda r: (
        worker.store.move_block(r["block_id"], r["tier"]), {})[-1])
    svc.unary("session_heartbeat", lambda r: {})
    svc.unary("persist_file", lambda r: {"fingerprint": worker.persist_file(
        r["ufs_path"], r["block_ids"], r.get("mount_id", 0))})

    svc.unary("cleanup_session", lambda r: (
        worker.cleanup_session(r["session_id"]), {})[-1])

    def get_metrics(req: dict) -> dict:
        """This worker's own registry, as ``get_metrics`` on the master
        serves the master's: timers with their percentiles, which the
        metrics heartbeat's ``Cluster.*`` roll-up drops."""
        from alluxio_tpu.metrics import metrics

        return {"metrics": metrics().snapshot()}

    svc.unary("get_metrics", get_metrics)
    return svc


class WorkerEndpoint:
    """A serving worker's listeners: the gRPC server and, beside it for
    same-host clients, the Unix-socket fast path (None when its socket
    could not be claimed, or was stopped)."""

    def __init__(self, server: RpcServer, fastpath, port: int) -> None:
        self.server = server
        self.fastpath = fastpath
        self.port = port

    def stop_fastpath(self) -> None:
        """Close the socket and unlink it; clients fall back to gRPC."""
        if self.fastpath is not None:
            self.fastpath.stop()
            self.fastpath = None

    def stop(self) -> None:
        self.stop_fastpath()
        self.server.stop()


def serve_worker(worker: BlockWorker, conf, *, bind_host: str,
                 port: int = 0) -> WorkerEndpoint:
    """Start serving ``worker``: the one way in for every way of
    running a worker (role process, in-process clusters). The worker's
    address takes the bound port, and the fast path is up before the
    caller registers the worker with the master, so a client that
    learns the address finds the socket. Every unary method rides it
    (``add_service`` leaves the streams to gRPC), under the gRPC
    server's own authenticator; a worker has no admission gate."""
    from alluxio_tpu.conf import Keys
    from alluxio_tpu.rpc.fastpath import serve_fastpath
    from alluxio_tpu.security.authentication import worker_authenticator

    authenticator = worker_authenticator(conf)
    server = RpcServer(bind_host=bind_host, port=port,
                       authenticator=authenticator)
    server.add_service(worker_service(worker))
    port = server.start()
    worker.address.rpc_port = port
    worker.address.data_port = port
    fastpath = serve_fastpath(
        server.services(), port, conf.get(Keys.MASTER_FASTPATH_DIR),
        authenticator=authenticator)
    return WorkerEndpoint(server, fastpath, port)
