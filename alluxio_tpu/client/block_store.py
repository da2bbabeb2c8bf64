"""Client-side block store: source selection + stream construction.

Re-design of ``core/client/fs/src/main/java/alluxio/client/block/
AlluxioBlockStore.java:63`` + the ladder in ``stream/BlockInStream.java:80-124``.
``open_block`` is that ladder, three rungs (``block_streams.py`` has the
streams): the same-host lease plane for a block in any tier of a
co-located worker, the gRPC stream from the nearest replica, the UFS
through a policy-chosen worker. Includes the **passive cache trigger**
(``AlluxioFileInStream.java:137`` triggerAsyncCaching): when a read was
served remotely or from UFS, ask the nearest local worker to cache the
block in the background.
"""

from __future__ import annotations

import socket
import threading
from typing import Dict, List, Optional, Set

from alluxio_tpu.client.block_streams import (
    BatchReadConf, BlockInStream, BlockOutStream, GrpcBlockInStream,
    GrpcBlockOutStream, LocalBlockOutStream, is_local_worker,
)
from alluxio_tpu.client.policy import BlockLocationPolicy
from alluxio_tpu.client.remote_read import RemoteReadConf, RemoteReadRuntime
from alluxio_tpu.client.shm_transport import ShmTransport
from alluxio_tpu.rpc.clients import BlockMasterClient, WorkerClient
from alluxio_tpu.utils import ids as id_utils
from alluxio_tpu.utils.exceptions import UnavailableError
from alluxio_tpu.utils.retry import ExponentialTimeBoundedRetry
from alluxio_tpu.utils.tracing import tracer
from alluxio_tpu.utils.wire import (
    BlockInfo, FileBlockInfo, FileInfo, TieredIdentity, WorkerInfo,
    WorkerNetAddress,
)


class BlockStoreClient:
    def __init__(self, block_master: BlockMasterClient, *,
                 identity: Optional[TieredIdentity] = None,
                 read_policy: Optional[BlockLocationPolicy] = None,
                 write_policy: Optional[BlockLocationPolicy] = None,
                 ufs_read_policy: Optional[BlockLocationPolicy] = None,
                 short_circuit: bool = True,
                 passive_cache: bool = True,
                 write_unavailable_window_s: float = 15.0,
                 streaming_chunk_size: int = 1 << 20,
                 streaming_writer_chunk_size: int = 1 << 20,
                 remote_read: Optional[RemoteReadConf] = None,
                 shm_cache_max: int = 64,
                 shm_renew_fraction: float = 0.5,
                 batch_read: Optional[BatchReadConf] = None,
                 native_fastpath: bool = True,
                 fastpath_dir: Optional[str] = None) -> None:
        """``fastpath_dir`` (``atpu.master.fastpath.dir``): where a
        same-host worker's RPC socket is probed, the ONE directory the
        master clients probe too; None = the servers' default.
        ``streaming_chunk_size``: per-message chunk of the gRPC read
        streams (``atpu.user.streaming.reader.chunk.size.bytes``);
        ``streaming_writer_chunk_size``: per-message chunk of the write
        stream (``atpu.user.streaming.writer.chunk.size.bytes``);
        ``remote_read``: striped-read tuning — the default conf stripes
        large remote reads, ``RemoteReadConf(stripe_size=0)`` pins the
        legacy single-stream path; ``short_circuit``
        (``atpu.user.short.circuit.enabled``): the same-host lease plane
        and the local write, off = every byte rides the remote rung;
        ``shm_cache_max`` / ``shm_renew_fraction`` (``atpu.user.shm.*``):
        the plane's segment cache and lazy renewal; ``batch_read``
        (``atpu.user.batch.read.*``): scatter/gather coalescing for
        ``pread_many`` on remote streams; ``native_fastpath``
        (``atpu.user.native.fastpath.enabled``): execute assembled
        read plans in C++ with the GIL released — the SHM batch flag
        lives here, the batch/striped flags ride their confs."""
        self._bm = block_master
        self._identity = identity or TieredIdentity.from_spec(
            None, hostname=socket.gethostname())
        self._read_policy = read_policy or BlockLocationPolicy.create(
            "LOCAL_FIRST", identity=self._identity)
        self._write_policy = write_policy or BlockLocationPolicy.create(
            "LOCAL_FIRST", identity=self._identity)
        self._ufs_read_policy = ufs_read_policy or BlockLocationPolicy.create(
            "DETERMINISTIC_HASH", shards=1)
        self._short_circuit = short_circuit
        self._passive_cache = passive_cache
        self._write_unavailable_window_s = write_unavailable_window_s
        self._chunk_size = max(1, streaming_chunk_size)
        self._writer_chunk_size = max(1, streaming_writer_chunk_size)
        #: the parallel remote-read runtime every GrpcBlockInStream of
        #: this store shares: stripe executor + per-worker latency EWMAs
        #: (hedging learns across reads, so it lives here, not per-stream)
        self.remote_read = RemoteReadRuntime(remote_read)
        self.session_id = id_utils.create_session_id()
        #: the same-host lease plane; None exactly when ``short_circuit``
        #: is off, which puts a same-host client on the remote rung
        self.shm = ShmTransport(
            self.session_id, cache_max=shm_cache_max,
            renew_fraction=shm_renew_fraction,
            host=socket.gethostname(),
            native_fastpath=native_fastpath) if short_circuit else None
        #: scatter/gather coalescing conf shared by every remote stream
        self.batch_read = batch_read if batch_read is not None \
            else BatchReadConf()
        #: worker that served the most recent write (sync-persist targets it;
        #: LOCAL_FIRST keeps one file's blocks on one worker)
        self.last_write_worker: Optional[WorkerClient] = None
        self.last_write_address: Optional[WorkerNetAddress] = None
        self._workers: Dict[str, WorkerClient] = {}
        self._fastpath_dir = fastpath_dir
        self._lock = threading.Lock()
        #: workers that recently failed reads, with the failure time —
        #: entries expire after _FAILED_WORKER_TTL_S so a recovered worker
        #: comes back into rotation (reference: AlluxioFileInStream
        #: failed-worker memory, :94-95)
        self._failed_workers: Dict[str, float] = {}

    @property
    def block_master(self):
        """The block-master client (public: placement reporting etc.)."""
        return self._bm

    # -- worker client cache -------------------------------------------------
    def worker_client(self, address: WorkerNetAddress) -> WorkerClient:
        key = f"{address.host}:{address.data_port or address.rpc_port}"
        with self._lock:
            c = self._workers.get(key)
            if c is None:
                c = WorkerClient(key, fastpath_dir=self._fastpath_dir)
                self._workers[key] = c
            return c

    _FAILED_WORKER_TTL_S = 30.0

    def _is_failed(self, key: str) -> bool:
        import time

        t = self._failed_workers.get(key)
        if t is None:
            return False
        if time.monotonic() - t > self._FAILED_WORKER_TTL_S:
            del self._failed_workers[key]
            return False
        return True

    def _live_workers(self) -> List[WorkerInfo]:
        return [w for w in self._bm.get_worker_infos()
                if not self._is_failed(w.address.key())]

    def mark_failed(self, address: Optional[WorkerNetAddress]) -> None:
        import time

        if address is not None:
            self._failed_workers[address.key()] = time.monotonic()

    # -- read ladder ---------------------------------------------------------
    def open_block(self, fbi: FileBlockInfo, *,
                   ufs_info: Optional[dict] = None,
                   cache_cold_reads: bool = True,
                   exclude: Optional[Set[str]] = None) -> BlockInStream:
        """Build the best stream for one block
        (reference: ``BlockInStream.create``, ``BlockInStream.java:97``).

        ``exclude``: worker address keys to skip for this call only (the
        caller saw a stale location there mid-retry)."""
        from alluxio_tpu.metrics import metrics

        info = fbi.block_info
        exclude = exclude or set()
        # 1) same-host cached copy, whatever tier holds it: one lease
        # RPC and one mmap, then every read is a memoryview slice
        if self.shm is not None:
            # which replicas are on this host: the host's name and a
            # stat of each candidate's shm dir, system calls that each
            # let go of the GIL (and wait to have it back)
            with tracer().span("atpu.block.same_host"):
                local_hostname = socket.gethostname()
                local = [loc for loc in info.locations
                         if loc.address.key() not in exclude
                         and is_local_worker(loc.address, local_hostname)]
            for loc in local:
                try:
                    stream = self.shm.open_stream(
                        self.worker_client(loc.address), info.block_id)
                except Exception:  # noqa: BLE001 - fall through ladder
                    # lease denied / block gone / map failed / worker
                    # dead (UnavailableError): the remote rung serves it
                    continue
                stream.address = loc.address
                metrics().counter("Client.BlockOpens.shm").inc()
                return stream
        # 2) remote cached copy, nearest first; the UFS descriptor rides
        # along so a stale location (block evicted since the master's last
        # heartbeat) self-heals server-side via read-through
        if info.locations:
            addrs = [l.address for l in info.locations
                     if not self._is_failed(l.address.key())
                     and l.address.key() not in exclude]
            if addrs:
                idx = self._identity.nearest(
                    [a.tiered_identity for a in addrs])
                address = addrs[idx if idx is not None else 0]
                # the whole healthy replica set rides along, nearest
                # first: striped reads fan stripes out across it, and a
                # replica dying mid-read re-routes instead of failing
                replicas = [address] + [a for a in addrs
                                        if a.key() != address.key()]
                stream = GrpcBlockInStream(
                    self.worker_client(address), info.block_id, info.length,
                    ufs=ufs_info, cache=cache_cold_reads,
                    chunk_size=self._chunk_size,
                    remote_read=self.remote_read, replicas=replicas,
                    client_factory=self.worker_client,
                    on_failed=self.mark_failed, batch=self.batch_read)
                stream.address = address
                metrics().counter("Client.BlockOpens.remote").inc()
                self._maybe_passive_cache(info, ufs_info)
                return stream
        # 3) UFS fallback through a policy-chosen worker (caches read-through)
        if ufs_info is None:
            raise UnavailableError(
                f"block {info.block_id} has no cached copy and no UFS source")
        workers = [w for w in self._live_workers()
                   if w.address.key() not in exclude]
        address = self._ufs_read_policy.pick(workers, block_id=info.block_id,
                                             block_size=info.length)
        if address is None:
            raise UnavailableError("no live workers for UFS read")
        # striping still applies on the cold path: the stripes coalesce
        # into ONE worker-side UFS fetch (ufs_fetch.py registry) but
        # stream back over pooled channels
        stream = GrpcBlockInStream(self.worker_client(address),
                                   info.block_id, info.length, ufs=ufs_info,
                                   cache=cache_cold_reads,
                                   chunk_size=self._chunk_size,
                                   remote_read=self.remote_read,
                                   client_factory=self.worker_client,
                                   on_failed=self.mark_failed,
                                   batch=self.batch_read)
        stream.address = address
        metrics().counter("Client.BlockOpens.ufs").inc()
        return stream

    def _maybe_passive_cache(self, info: BlockInfo,
                             ufs_info: Optional[dict]) -> None:
        """Reading remotely: ask a local worker to cache a copy
        (reference: AsyncCache RPC, ``AlluxioFileInStream.java:137``)."""
        if not self._passive_cache or ufs_info is None:
            return
        local_hostname = socket.gethostname()
        for w in self._live_workers():
            if is_local_worker(w.address, local_hostname) and not any(
                    loc.address.key() == w.address.key()
                    for loc in info.locations):
                try:
                    self.worker_client(w.address).async_cache(
                        info.block_id, ufs_info["ufs_path"],
                        ufs_info["offset"], ufs_info["length"],
                        ufs_info.get("mount_id", 0))
                except Exception:  # noqa: BLE001 - best effort
                    pass
                return

    # -- write ---------------------------------------------------------------
    def _pick_writable(self, block_id: int, size_hint: int,
                       preferred: Optional[WorkerNetAddress]
                       ) -> Optional[WorkerNetAddress]:
        # Unfiltered list: the failed memory records READ errors (30s
        # TTL); a worker that botched one read is still a valid write
        # target, and filtering it here could starve the retry window.
        workers = list(self._bm.get_worker_infos())
        if preferred is not None and any(
                w.address.key() == preferred.key() for w in workers):
            # one file's blocks stay on one worker so worker-side persist
            # can stream them out locally (reference: LocalFirstPolicy
            # stickiness within a FileOutStream)
            return preferred
        return self._write_policy.pick(workers, block_id=block_id,
                                       block_size=size_hint)

    def open_block_writer(self, block_id: int, *, size_hint: int,
                          tier: str = "", pinned: bool = False,
                          preferred: Optional[WorkerNetAddress] = None
                          ) -> BlockOutStream:
        address = self._pick_writable(block_id, size_hint, preferred)
        if address is None and self._write_unavailable_window_s > 0:
            # Transient unavailability: a worker that missed heartbeats
            # under host overload is marked lost, empties the live set,
            # then re-registers seconds later. Wait out that window with
            # jittered backoff instead of failing the stream (reference:
            # client write retry on UnavailableException).
            policy = ExponentialTimeBoundedRetry(
                max_duration_s=self._write_unavailable_window_s,
                base_sleep_s=0.05, max_sleep_s=1.0)
            policy.attempt()  # first attempt already happened above
            while address is None and policy.attempt():
                address = self._pick_writable(block_id, size_hint, preferred)
        if address is None:
            raise UnavailableError("no live workers to write to")
        client = self.worker_client(address)
        self.last_write_worker = client
        self.last_write_address = address
        if self._short_circuit and is_local_worker(address,
                                                   socket.gethostname()):
            try:
                return LocalBlockOutStream(client, self.session_id, block_id,
                                           size_hint=size_hint, tier=tier,
                                           pinned=pinned)
            except Exception:  # noqa: BLE001
                pass
        return GrpcBlockOutStream(client, self.session_id, block_id,
                                  tier=tier, pinned=pinned,
                                  chunk_size=self._writer_chunk_size)

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        self.remote_read.close()
        if self.shm is not None:
            # unmap everything client-side; the cleanup_session calls
            # below release the leases gracefully on each worker
            # (worker-side close_session), TTL expiry backstops the rest
            self.shm.close()
        for c in self._workers.values():
            try:
                c.cleanup_session(self.session_id)
            except Exception:  # noqa: BLE001
                pass
