"""Per-block data streams: the transport decision ladder.

Re-design of ``core/client/fs/src/main/java/alluxio/client/block/stream/
{BlockInStream.java:97,LocalFileDataReader.java:41,GrpcDataReader.java:49,
LocalFileDataWriter,GrpcDataWriter}.java``:

Read ladder (closest wins; ``BlockStoreClient.open_block`` walks it):
1. **Same host: the lease plane** — block cached in any tier of a
   same-host worker: lease its file (``shm_open``), mmap it once, and
   serve every read as a slice of the mapping
   (``shm_transport.ShmBlockInStream``). Zero RPC per byte, zero copy;
   the mapped pages can be handed to ``jax.device_put`` directly. A
   denied lease or a failed map falls to rung 2.
2. **gRPC stream** — cached on a remote worker (``GrpcBlockInStream``;
   ``choose_route`` picks per-op / batch / striped / single stream).
3. **UFS fallback through a worker** — not cached anywhere: a
   policy-chosen worker read-throughs from the UFS (caching it), client
   streams from that worker.

Write ladder mirrors it: short-circuit file write locally, gRPC stream
remotely.
"""

from __future__ import annotations

import os
import queue
import socket
import threading
from concurrent import futures
from typing import Iterator, List, NamedTuple, Optional, Sequence

import numpy as np

from alluxio_tpu.client.remote_read import choose_route
from alluxio_tpu.rpc.clients import WorkerClient
from alluxio_tpu.utils.exceptions import UnavailableError
from alluxio_tpu.utils.wire import BlockInfo, WorkerNetAddress


#: cached ``metrics()`` accessor: the import machinery (sys.modules
#: lookup + attribute walk) was paid inside ``_record_read`` on EVERY
#: read — hot-path cost for a value that never changes. The function
#: (not the registry) is cached so ``reset_metrics()`` in tests still
#: takes effect.
_metrics_fn = None


def _metrics():
    global _metrics_fn
    if _metrics_fn is None:
        # deferred: alluxio_tpu.metrics imports are cyclic at module
        # load time (metrics sinks reach back into client config)
        from alluxio_tpu.metrics import metrics as fn

        _metrics_fn = fn
    return _metrics_fn()


def _record_read(bucket: str, nbytes: int) -> None:
    """Per-source read accounting: ``Client.BytesRead.<bucket>`` /
    ``Client.BlocksRead.<bucket>`` counters (additive — they roll up to
    ``Cluster.*`` on the metrics heartbeat)."""
    m = _metrics()
    m.counter(f"Client.BytesRead.{bucket}").inc(nbytes)
    m.counter(f"Client.BlocksRead.{bucket}").inc()


class BatchReadConf(NamedTuple):
    """Scatter/gather coalescing knobs (``atpu.user.batch.read.*``)."""

    enabled: bool = True
    max_op_bytes: int = 64 << 10
    max_ops: int = 256
    #: scatter read_many responses through the native plan executor
    #: (``atpu.user.native.fastpath.enabled``); pure-Python fallback is
    #: byte-identical
    native_fastpath: bool = True

    @classmethod
    def from_conf(cls, conf) -> "BatchReadConf":
        from alluxio_tpu.conf import Keys

        return cls(
            enabled=conf.get_bool(Keys.USER_BATCH_READ_ENABLED),
            max_op_bytes=conf.get_bytes(Keys.USER_BATCH_READ_MAX_OP_BYTES),
            max_ops=max(1, conf.get_int(Keys.USER_BATCH_READ_MAX_OPS)),
            native_fastpath=conf.get_bool(
                Keys.USER_NATIVE_FASTPATH_ENABLED))


def is_local_worker(address: WorkerNetAddress, local_hostname: str) -> bool:
    """Same-host check gate for the lease plane and the short-circuit
    write: the worker's shm dir must be a real local directory."""
    if address.host not in (local_hostname, "localhost", "127.0.0.1",
                            socket.gethostname()):
        return False
    return bool(address.shm_dir) and os.path.isdir(address.shm_dir)


class BlockInStream:
    """Positioned reads over one block."""

    def __init__(self, block_id: int, length: int) -> None:
        self.block_id = block_id
        self.length = length
        #: serving worker (set by BlockStoreClient); failed-worker retry
        #: marks it when a read dies mid-stream
        self.address = None
        #: raw serving source of the LAST read: a worker tier alias
        #: ("MEM"/"SSD"/...), "SHM" for the same-host lease plane, or "UFS"
        self.last_source: Optional[str] = None

    def pread(self, offset: int, n: int) -> bytes:
        raise NotImplementedError

    def read_all(self) -> bytes:
        return self.pread(0, self.length)

    def pread_many(self, offsets: Sequence[int],
                   sizes: Sequence[int]) -> List[bytes]:
        """Scatter/gather: N positioned reads, results in request
        order. The base implementation is the per-op loop —
        byte-identical to calling :meth:`pread` N times; transports
        that can coalesce (``GrpcBlockInStream`` -> ``read_many`` RPC)
        override it."""
        return [self.pread(off, n) for off, n in zip(offsets, sizes)]

    def memoryview(self) -> Optional[memoryview]:
        """Zero-copy view when the source is local; None otherwise."""
        return None

    @property
    def source(self) -> str:
        raise NotImplementedError

    def source_bucket(self) -> str:
        """The last read's serving source, normalized to an input-doctor
        bucket: ``shm`` (same-host mmap under a lease), ``remote`` (cached on
        a remote worker, whatever its tier), ``ufs`` (cold
        read-through), or ``unknown``."""
        src = self.last_source
        if src is None:
            return "unknown"
        if src == "SHM":
            return "shm"
        if src == "UFS":
            return "ufs"
        return "remote"

    def stale(self) -> bool:
        """True when the transport under this stream dropped what it
        serves from (an SHM segment released by the segment cache's
        LRU): a holder of cached streams re-opens through the routing
        ladder instead of reading it."""
        return False

    def close(self) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class GrpcBlockInStream(BlockInStream):
    """Remote read over gRPC chunk streams
    (reference: ``GrpcDataReader.java:49``).

    Reads larger than one stripe ride the parallel data plane
    (``client/remote_read.py``): concurrent range streams across the
    block's replica set — or pooled channels to a single worker — with
    hedged stragglers and zero-join ``memoryview`` assembly into one
    preallocated buffer. Smaller reads (and a runtime configured with
    ``stripe.size=0``) take the legacy single-stream loop, byte for
    byte what the seed shipped."""

    source = "REMOTE"

    def __init__(self, worker: WorkerClient, block_id: int, length: int,
                 *, ufs: Optional[dict] = None, cache: bool = True,
                 chunk_size: int = 1 << 20, remote_read=None,
                 replicas: Optional[list] = None, client_factory=None,
                 on_failed=None,
                 batch: Optional[BatchReadConf] = None) -> None:
        """``remote_read``: a ``RemoteReadRuntime`` (None = legacy only);
        ``replicas``: the block's location addresses, nearest first;
        ``client_factory``: address -> WorkerClient for replica fan-out;
        ``on_failed``: callback(address) when a worker dies mid-stripe
        (``BlockStoreClient.mark_failed`` plumbing);
        ``batch``: scatter/gather coalescing (None = per-op only)."""
        super().__init__(block_id, length)
        self._worker = worker
        self._ufs = ufs
        self._cache = cache
        self._chunk = chunk_size
        self._remote_read = remote_read
        self._replicas = replicas or []
        self._client_factory = client_factory
        self._on_failed = on_failed
        self._batch = batch

    # -- parallel data plane -------------------------------------------------
    def _striped_sources(self, conf):
        """Build the stripe fan-out: one source per replica (rotating
        onto pooled channels when concurrency exceeds the replica
        count), or ``concurrency`` pooled channels to the single
        serving worker."""
        from alluxio_tpu.client.remote_read import (
            MAX_POOLED_CHANNELS, GrpcReadSource,
        )

        addrs = [a for a in self._replicas if a is not None]
        if not addrs:
            if self.address is None:
                return []
            addrs = [self.address]
        fan_out = max(len(addrs), min(conf.concurrency,
                                      MAX_POOLED_CHANNELS * len(addrs)))
        sources = []
        for i in range(fan_out):
            addr = addrs[i % len(addrs)]
            channel = i // len(addrs)
            if self.address is not None and addr.key() == self.address.key():
                worker = self._worker
            elif self._client_factory is not None:
                worker = self._client_factory(addr)
            else:
                continue
            sources.append(GrpcReadSource(
                worker, addr, channel, block_id=self.block_id,
                ufs=self._ufs, cache=self._cache))
        return sources

    def _striped_read(self, offset: int, n: int):
        rt = self._remote_read
        read = rt.read(block_id=self.block_id,
                       sources=self._striped_sources(rt.conf),
                       offset=offset, length=n, chunk_size=self._chunk,
                       on_failed=self._on_failed)
        view = read.read_view()
        self.last_source = read.source_tag or "REMOTE"
        _record_read(self.source_bucket(), len(view))
        return view

    def _use_striped(self, n: int) -> bool:
        rt = self._remote_read
        return rt is not None and rt.enabled and \
            choose_route(n, striped=rt.conf) == "striped"

    def pread(self, offset: int, n: int) -> bytes:
        n = max(0, min(n, self.length - offset))
        if self._use_striped(n):
            return bytes(self._striped_read(offset, n))
        out = bytearray()
        source = None
        for msg in self._worker.read_block(
                self.block_id, offset=offset, length=n,
                chunk_size=self._chunk, ufs=self._ufs, cache=self._cache):
            out.extend(msg["data"])
            source = msg.get("source", source)
        # a pre-source-tagging worker sends no field: the read still
        # went to a remote worker's cache (cold reads raise without a
        # UFS descriptor, and with one the worker tags "UFS")
        self.last_source = source or "REMOTE"
        _record_read(self.source_bucket(), len(out))
        return bytes(out)

    def pread_many(self, offsets: Sequence[int],
                   sizes: Sequence[int]) -> List[bytes]:
        """Small-op batches coalesce into ``read_many`` RPCs: one wire
        round trip and ONE response buffer per ``max_ops`` ops instead
        of an RPC per op — the random-4k fix (docs/small_reads.md).
        Ineligible ops (too large, cold block needing a UFS descriptor,
        batching off) and any RPC failure take the per-op path, which
        is byte-identical by construction."""
        b = self._batch
        # choose_route decides per the routing matrix; the stream adds
        # its own constraint: cold blocks (UFS descriptor present) need
        # the read-through stream, so they stay per-op
        eligible = (self._ufs is None and len(sizes) > 0 and choose_route(
            max(sizes), batch=b, batch_ops=len(offsets)) == "batch")
        if not eligible:
            return super().pread_many(offsets, sizes)
        try:
            return self._batched_pread_many(offsets, sizes, b.max_ops)
        except Exception:  # noqa: BLE001 - transparent per-op fallback
            _metrics().counter("Client.BatchReadFallbacks").inc()
            return super().pread_many(offsets, sizes)

    def _batched_pread_many(self, offsets: Sequence[int],
                            sizes: Sequence[int],
                            max_ops: int) -> List[bytes]:
        import time as _time

        from alluxio_tpu.utils.tracing import current_span

        m = _metrics()
        sp = current_span()
        resps: List[dict] = []
        for i in range(0, len(offsets), max_ops):
            offs = list(offsets[i:i + max_ops])
            szs = [max(0, min(s, self.length - off))
                   for off, s in zip(offs, sizes[i:i + max_ops])]
            t0 = _time.perf_counter()
            resp = self._worker.read_many(self.block_id, offs, szs)
            if sp is not None:
                sp.phase("wire", (_time.perf_counter() - t0) * 1000.0)
            resps.append(resp)
            self.last_source = resp.get("source") or "REMOTE"
            m.counter("Client.BatchReadBatches").inc()
            m.counter("Client.BatchReadOps").inc(len(offs))
        out = self._scatter_responses(resps)
        total = sum(len(b) for b in out)
        m.counter("Client.BatchReadBytes").inc(total)
        _record_read(self.source_bucket(), total)
        return out

    def _scatter_responses(self, resps: List[dict]) -> List[bytes]:
        """Cut the collected ``read_many`` payloads into per-op bytes.
        With the fastpath on, all responses scatter into ONE dest
        buffer through a single GIL-free native call; the pure-Python
        slice loop below is the byte-identical fallback."""
        nops = sum(len(r["lengths"]) for r in resps)
        if self._batch is not None and self._batch.native_fastpath \
                and nops > 1:
            from alluxio_tpu.client import fastpath

            if fastpath.available():
                try:
                    return self._native_scatter(resps, nops)
                except fastpath.NativeExecError:
                    pass  # Client.NativeFallbacks already counted
            else:
                fastpath.note_unavailable()
        out: List[bytes] = []
        for resp in resps:
            buf = memoryview(resp["data"])
            pos = 0
            for n in resp["lengths"]:
                out.append(bytes(buf[pos:pos + n]))
                pos += n
        return out

    def _native_scatter(self, resps: List[dict], nops: int) -> List[bytes]:
        from alluxio_tpu import native
        from alluxio_tpu.client import fastpath

        lens = np.fromiter((n for r in resps for n in r["lengths"]),
                           dtype=np.int64, count=nops)
        bounds = np.zeros(nops + 1, dtype=np.int64)
        np.cumsum(lens, out=bounds[1:])
        ops = fastpath.op_table(nops)
        ops["len"] = lens  # kind zero-init == OP_COPY
        ops["dst_off"] = bounds[:-1]
        keep = []
        row = 0
        for resp in resps:
            k = len(resp["lengths"])
            loc = native._buffer_address(resp["data"])
            if loc is None:
                raise fastpath.NativeExecError("no payload address")
            addr, n, ka = loc
            keep.append(ka)
            ops["src"][row:row + k] = addr
            ops["src_len"][row:row + k] = n
            # offsets within this response = global dest offsets
            # rebased to the response's first op
            ops["src_off"][row:row + k] = \
                bounds[row:row + k] - bounds[row]
            row += k
        dest = bytearray(int(bounds[-1]))
        fastpath.execute_table(ops, dest, host="batch")
        del keep
        return fastpath.slice_out(dest, bounds.tolist())

    def read_all_view(self) -> memoryview:
        """The whole block as a buffer view: striped reads hand back
        their preallocated assembly buffer with NO final copy —
        ``numpy.frombuffer``/``jax.device_put`` consume it zero-copy.
        The legacy path wraps its joined bytes (one view, same data)."""
        if self._use_striped(self.length):
            return self._striped_read(0, self.length)
        return memoryview(self.pread(0, self.length))

    @property
    def is_ufs_fallback(self) -> bool:
        return self._ufs is not None


class BlockOutStream:
    def __init__(self, block_id: int) -> None:
        self.block_id = block_id
        self.written = 0

    def write(self, data: bytes) -> None:
        raise NotImplementedError

    def close(self, cancel: bool = False) -> None:
        raise NotImplementedError

    def __enter__(self):
        return self

    def __exit__(self, exc_type, *exc):
        self.close(cancel=exc_type is not None)
        return False


class LocalBlockOutStream(BlockOutStream):
    """Short-circuit write: append straight to the worker's temp file
    (reference: ``LocalFileDataWriter`` + ``CreateLocalBlock`` lease)."""

    def __init__(self, worker: WorkerClient, session_id: int, block_id: int,
                 *, size_hint: int, tier: str = "", pinned: bool = False):
        super().__init__(block_id)
        self._worker = worker
        self._session = session_id
        self._pinned = pinned
        path = worker.create_local_block(session_id, block_id,
                                         size_hint=size_hint, tier=tier)
        self._f = open(path, "wb")
        self._closed = False

    def write(self, data: bytes) -> None:
        self._f.write(data)
        self.written += len(data)

    def close(self, cancel: bool = False) -> None:
        if self._closed:
            return
        self._closed = True
        self._f.flush()
        os.fsync(self._f.fileno())
        self._f.close()
        self._worker.complete_local_block(self._session, self.block_id,
                                          cancel=cancel, pinned=self._pinned)


class GrpcBlockOutStream(BlockOutStream):
    """Remote write: chunks ride the client-stream as they are produced —
    a bounded queue feeds the in-flight RPC so network transfer overlaps
    the producer and peak memory stays ~queue-depth chunks, not a whole
    block (reference: ``GrpcDataWriter`` chunked flow control)."""

    _QUEUE_DEPTH = 4
    _CHUNK = 1 << 20

    def __init__(self, worker: WorkerClient, session_id: int, block_id: int,
                 *, tier: str = "", pinned: bool = False,
                 chunk_size: Optional[int] = None) -> None:
        super().__init__(block_id)
        self._worker = worker
        self._session = session_id
        self._tier = tier
        self._pinned = pinned
        self._chunk = max(1, chunk_size) if chunk_size else self._CHUNK
        self._queue: "queue.Queue" = queue.Queue(maxsize=self._QUEUE_DEPTH)
        self._result: "futures.Future" = futures.Future()
        self._sender = threading.Thread(target=self._send, daemon=True,
                                        name=f"block-writer-{block_id}")
        self._sender.start()
        self._closed = False

    def _send(self) -> None:
        def gen():
            yield {"block_id": self.block_id, "session_id": self._session,
                   "tier": self._tier, "pinned": self._pinned}
            while True:
                item = self._queue.get()
                if item is None:
                    return
                yield {"data": item}

        try:
            resp = self._worker._channel.call_stream_in(
                self._worker.service, "write_block", gen())
            self._result.set_result(resp["length"])
        except BaseException as e:  # noqa: BLE001 - delivered on close()
            self._result.set_exception(e)
            # unblock a producer stuck on a full queue
            while True:
                try:
                    self._queue.get_nowait()
                except queue.Empty:
                    break

    def write(self, data: bytes) -> None:
        view = memoryview(data)
        for i in range(0, len(view), self._chunk):
            if self._result.done():  # sender died: surface its error
                self._result.result()
            self._queue.put(bytes(view[i:i + self._chunk]))
        self.written += len(data)

    def close(self, cancel: bool = False) -> None:
        if self._closed:
            return
        self._closed = True
        self._queue.put(None)
        if cancel:
            # worker-side temp block is reaped by session cleanup; just
            # stop feeding and drop the RPC result
            try:
                self._result.result(timeout=30)
            except Exception:  # noqa: BLE001
                pass
            return
        n = self._result.result(timeout=300)
        if n != self.written:
            raise UnavailableError(
                f"short write: {n} of {self.written} bytes for block "
                f"{self.block_id}")
