"""Client side of the same-host zero-copy plane: SHM segment transport.

A co-located client leases a block's file from the worker, in whatever
tier holds it (``shm_open``), mmaps it ONCE, and serves every read of
that block as a ``memoryview`` slice over the shared pages — zero RPCs,
zero serialization, zero copies per read. ``numpy_view`` hands the same pages
to ``np.frombuffer`` for a single ``jax.device_put`` (the only copy a
same-host read ever pays is host->device). See ``alluxio_tpu/shm/`` for
the lease protocol and docs/small_reads.md for the design.

The transport keeps an LRU **segment cache**
(``atpu.user.shm.segment.cache.max``): repeated opens of a hot block —
the shuffled-small-read pattern the subsystem exists for — cost a dict
hit, not an RPC. Leases renew *lazily*: a read touching a segment past
``atpu.user.shm.lease.renew.fraction`` of its TTL fires one
``shm_renew``, amortized over every read in between.

A miss that finds the cache at its bound makes room BEFORE it leases:
the LRU victim leaves the cache first and goes to the transport's own
daemon thread (started at the first eviction), which unmaps it and
gives its lease back, so neither stands between the opener and the
block it came for. The ``munmap`` still holds up whatever the opener
asks of the same address space meanwhile (a page-table fill anywhere;
on gVisor every system call, the lease's ``send`` too: PERF.md), so
what leaves the opener's path is the release and the waiting, not the
``munmap``'s own time. More than ``_RELEASE_BACKLOG`` victims waiting
and the opener releases its own in line.

Failure contract (the fallback matrix in docs/small_reads.md): every
exit from this plane is a typed error the routing layer catches —
``ShmLeaseDeniedError`` / ``ShmSegmentUnavailableError`` from the
worker, ``OSError`` from a failed map (or the injected
``atpu.debug.fault.shm.map.error.rate``). A *renewal* failure on an
already-mapped segment is NOT an error: Linux keeps mmapped pages valid
across an unlink, so in-flight readers finish safely and only the next
cold open re-routes.
"""

from __future__ import annotations

import mmap
import os
import queue
import threading
import time
from collections import OrderedDict
from typing import Optional

import numpy as np

from alluxio_tpu.client.block_streams import BlockInStream, _record_read
from alluxio_tpu.rpc.clients import WorkerClient
from alluxio_tpu.utils.tracing import tracer


class ShmSegment:
    """One mapped segment: mmap + lease bookkeeping."""

    __slots__ = ("block_id", "path", "length", "lease_id", "ttl_s",
                 "renew_at", "mm", "dead")

    def __init__(self, block_id: int, path: str, length: int,
                 lease_id: int, ttl_s: float, renew_fraction: float,
                 mm: Optional[mmap.mmap]) -> None:
        self.block_id = block_id
        self.path = path
        self.length = length
        self.lease_id = lease_id
        self.ttl_s = ttl_s
        self.renew_at = time.monotonic() + ttl_s * renew_fraction
        self.mm = mm
        #: lease lost (renewal refused / released): serve existing maps,
        #: stop cache hits
        self.dead = False

    @property
    def released(self) -> bool:
        """The map is gone (segment-cache LRU turnover, invalidation):
        a non-empty block must be leased again, never read from here."""
        return self.mm is None and self.length > 0

    def _gone(self) -> Exception:
        from alluxio_tpu.shm import ShmSegmentUnavailableError

        return ShmSegmentUnavailableError(
            f"SHM segment of block {self.block_id} was released; "
            f"open the block again")

    def live_map(self) -> Optional[mmap.mmap]:
        """The mapping, or None for an empty block; a released segment
        raises the typed fallback error — it used to read as an EMPTY
        block, silently."""
        mm = self.mm  # once: the transport may close it under us
        if mm is None and self.length > 0:
            raise self._gone()
        return mm

    def export(self, wrap):
        """``wrap(mapping)`` (a ``memoryview``, an ndarray over the
        pages), or None for an empty block. The releaser thread may
        close the mapping between the look and the wrap: that is a
        released segment too, not a ``ValueError``."""
        mm = self.live_map()
        if mm is None:
            return None
        try:
            return wrap(mm)
        except ValueError:
            if mm.closed:
                raise self._gone() from None
            raise

    def view(self, offset: int = 0, length: int = -1) -> memoryview:
        whole = self.export(memoryview)
        if whole is None:
            return memoryview(b"")
        end = self.length if length < 0 else min(self.length,
                                                 offset + length)
        return whole[offset:max(offset, end)]

    def close_map(self) -> None:
        """Drop the mapping; the segment serves no cache hit again."""
        self.dead = True
        mm, self.mm = self.mm, None
        if mm is not None:
            try:
                mm.close()
            except BufferError:
                # a numpy view is still live (in-flight device_put);
                # leave the mapping to GC — pages stay valid on Linux.
                # That munmap runs when the last view dies, under no
                # span: counted, so a reader of atpu.shm.unmap knows
                # how many it did not see
                from alluxio_tpu.metrics import metrics

                metrics().counter("Client.ShmUnmapDeferred").inc()


#: victims handed to the transport's thread and not yet released each
#: hold a mapping and a worker-side lease: past this many the opener
#: releases its victim in line, so mapped memory stays within
#: ``cache_max`` + this many segments
_RELEASE_BACKLOG = 4


class ShmTransport:
    """Per-process segment cache + lease manager."""

    def __init__(self, session_id: int, *, cache_max: int = 64,
                 renew_fraction: float = 0.5, host: str = "",
                 native_fastpath: bool = True) -> None:
        self._session = session_id
        self._cache_max = max(1, int(cache_max))
        self._renew_fraction = min(0.95, max(0.05, float(renew_fraction)))
        self._host = host
        #: batch pread_many through the native plan executor
        #: (``atpu.user.native.fastpath.enabled``); the per-op Python
        #: loop stays as the byte-identical fallback
        self.native_fastpath = bool(native_fastpath)
        self._lock = threading.Lock()
        self._segments: "OrderedDict[int, ShmSegment]" = OrderedDict()
        #: evicted segments on their way to the releaser thread, which
        #: the first eviction starts; ``_backlog`` counts those handed
        #: off and not yet released (under ``_lock``; ``_released_cv``
        #: tells ``drain``)
        self._victims: "queue.SimpleQueue" = queue.SimpleQueue()
        self._releaser: Optional[threading.Thread] = None
        self._backlog = 0
        self._released_cv = threading.Condition(self._lock)

    # -------------------------------------------------------------- open
    def open_stream(self, worker: WorkerClient, block_id: int
                    ) -> "ShmBlockInStream":
        """The same-host read stream; raises the typed fallback errors
        (lease denied / segment unavailable / map OSError) the routing
        ladder in ``BlockStoreClient.open_block`` catches."""
        return ShmBlockInStream(self, worker, self.segment(worker,
                                                           block_id))

    def segment(self, worker: WorkerClient, block_id: int) -> ShmSegment:
        with self._lock:
            seg = self._segments.get(block_id)
            if seg is not None and not seg.dead:
                self._segments.move_to_end(block_id)
            else:
                seg = None
        if seg is not None:
            self.maybe_renew(worker, seg)
            if not seg.dead:
                return seg
            self.invalidate(block_id)
        return self._map(worker, block_id)

    def _map(self, worker: WorkerClient, block_id: int) -> ShmSegment:
        from alluxio_tpu.metrics import metrics
        from alluxio_tpu.utils import faults

        span = tracer().span
        # room first: the victim's munmap and release run on the
        # releaser thread, not between this thread and the block it
        # came for. A lease or a map that then fails leaves the cache
        # one under its bound
        with self._lock:
            victims = self._pop_lru(self._cache_max - 1)
        self._evict(worker, victims)
        # lease grant: the worker pins the block against eviction before
        # we touch the file — typed denials propagate to the router
        with span("atpu.shm.lease"):
            lease = worker.shm_open(self._session, block_id)
        try:
            with span("atpu.shm.map"):
                if faults.armed() and \
                        faults.injector().take_shm_map_error(self._host):
                    raise OSError(
                        f"injected shm map fault for block {block_id}")
                if lease["length"] > 0:
                    f = open(lease["path"], "rb")
                    try:
                        mm = mmap.mmap(f.fileno(), 0, prot=mmap.PROT_READ)
                    finally:
                        f.close()
                else:
                    mm = None
        except OSError:
            metrics().counter("Client.ShmMapFailures").inc()
            # we hold a lease we cannot use; give it back now rather
            # than waiting out the TTL
            self._give_back(worker, lease["lease_id"])
            raise
        seg = ShmSegment(block_id, lease["path"], lease["length"],
                         lease["lease_id"], lease["ttl_s"],
                         self._renew_fraction, mm)
        lost = None
        with self._lock:
            held = self._segments.get(block_id)
            if held is not None and not held.dead:
                # another thread leased and mapped this block since our
                # look-up missed: one segment a block. Ours goes back
                # now; overwriting the entry would orphan ITS lease
                # until the TTL
                lost, seg = seg, held
            else:
                self._segments[block_id] = seg
            self._segments.move_to_end(block_id)
            # another thread filled the slot we made: trim the same way
            victims = self._pop_lru(self._cache_max)
        if lost is not None:
            # rare, and its pages were never touched: in line
            with span("atpu.shm.evict", reason="lost_race"):
                self._release(worker, lost)
        self._evict(worker, victims)
        return seg

    # ----------------------------------------------------------- eviction
    def _pop_lru(self, keep: int) -> list:
        """The cache's oldest segments beyond ``keep``, out of it
        (the caller holds ``_lock``)."""
        victims = []
        while len(self._segments) > keep:
            victims.append(self._segments.popitem(last=False)[1])
        return victims

    def _evict(self, worker: WorkerClient, victims: list) -> None:
        """What the cache's bound pushed out goes to the releaser
        thread: the opener pays the hand-off alone (the span). Where
        ``_RELEASE_BACKLOG`` already wait it releases its victim
        itself, which is the back-pressure."""
        from alluxio_tpu.metrics import metrics

        for v in victims:
            with self._lock:
                handoff = self._backlog < _RELEASE_BACKLOG
                if handoff:
                    self._backlog += 1
                    if self._releaser is None:
                        self._releaser = threading.Thread(
                            target=self._release_loop,
                            name="atpu-shm-release", daemon=True)
                        self._releaser.start()
            if handoff:
                with tracer().span("atpu.shm.evict", reason="lru"):
                    self._victims.put((worker, v))
                metrics().counter("Client.ShmEvictHandoffs").inc()
            else:
                with tracer().span("atpu.shm.evict", reason="inline"):
                    self._release(worker, v)
                metrics().counter("Client.ShmEvictInline").inc()

    def _release_loop(self) -> None:
        """The releaser thread: one victim at a time until ``close``'s
        sentinel. It brings its own fast-path connection (one a thread,
        ``rpc/fastpath.py``)."""
        while True:
            item = self._victims.get()
            if item is None:
                return
            try:
                self._release(*item)
            except Exception:  # noqa: BLE001 - the next victim still goes
                pass
            finally:
                with self._released_cv:
                    self._backlog -= 1
                    self._released_cv.notify_all()

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait until every victim handed off has been released."""
        with self._released_cv:
            return self._released_cv.wait_for(
                lambda: self._backlog == 0, timeout)

    # ------------------------------------------------------------- leases
    def maybe_renew(self, worker: WorkerClient, seg: ShmSegment) -> None:
        """Lazy renewal, on every open and read of a cached segment: one
        RPC past the renew point, amortized over the zero-copy reads in
        between. A refused renewal (worker restarted, lease reclaimed)
        marks the segment dead — existing views stay valid (mmap
        semantics), the next open re-leases."""
        if seg.dead or time.monotonic() < seg.renew_at:
            return
        try:
            resp = worker.shm_renew(self._session, seg.lease_id)
        except Exception:  # noqa: BLE001 - worker gone: segment is stale
            seg.dead = True
            return
        if resp.get("ok"):
            seg.renew_at = time.monotonic() + \
                float(resp.get("ttl_s", seg.ttl_s)) * self._renew_fraction
        else:
            seg.dead = True

    def _release(self, worker: WorkerClient, seg: ShmSegment) -> None:
        # the munmap of the whole mapping (left to the collector where
        # a view is still live: Client.ShmUnmapDeferred)
        with tracer().span("atpu.shm.unmap", bytes=seg.length):
            seg.close_map()
        self._give_back(worker, seg.lease_id)

    def _give_back(self, worker: WorkerClient, lease_id: int) -> None:
        """The lease back to the worker, as the client sees the RPC:
        the twin of ``atpu.shm.lease`` (the worker's own part is the
        timer ``Worker.RpcServeTime.shm_release``)."""
        with tracer().span("atpu.shm.release"):
            try:
                worker.shm_release(self._session, lease_id)
            except Exception:  # noqa: BLE001 - TTL reclaims it anyway
                pass

    def invalidate(self, block_id: int) -> None:
        with self._lock:
            seg = self._segments.pop(block_id, None)
        if seg is not None:
            seg.close_map()

    def close(self) -> None:
        """Let the releaser thread finish what waits and end, then
        unmap everything. The leases still out go with the session
        (``cleanup_session`` on each worker), else with their TTL."""
        with self._lock:
            releaser, self._releaser = self._releaser, None
            segs = list(self._segments.values())
            self._segments.clear()
        if releaser is not None:
            self._victims.put(None)
            releaser.join()
        for seg in segs:
            seg.close_map()

    def holds(self, block_id: int) -> bool:
        """Whether the cache maps ``block_id`` now, so that an open of
        it would be a dictionary look and no lease."""
        with self._lock:
            seg = self._segments.get(block_id)
            return seg is not None and not seg.dead

    def cached_blocks(self) -> int:
        with self._lock:
            return len(self._segments)


class ShmBlockInStream(BlockInStream):
    """Same-host zero-copy stream over a cached SHM segment.

    Reads are ``memoryview`` slices of shared pages: no RPC, no
    serialization — the read-path microscope shows zero ``serialize`` /
    ``wire`` phase time here, which `make bench-smallread` asserts.
    ``close`` is the base's no-op: the segment stays cached (and leased)
    for the next open, ``BlockStoreClient.close`` releases it."""

    source = "LOCAL"

    def __init__(self, transport: ShmTransport, worker: WorkerClient,
                 seg: ShmSegment) -> None:
        super().__init__(seg.block_id, seg.length)
        self.last_source = "SHM"
        self._transport = transport
        self._worker = worker
        self._seg = seg

    def stale(self) -> bool:
        return self._seg.released

    def pread(self, offset: int, n: int) -> bytes:
        return bytes(self.pread_view(offset, n))

    def pread_view(self, offset: int, n: int) -> memoryview:
        """The zero-copy form of :meth:`pread`: a live view of the
        shared pages, no intermediate ``bytes``."""
        self._transport.maybe_renew(self._worker, self._seg)
        out = self._seg.view(offset, n)
        from alluxio_tpu.metrics import metrics

        metrics().counter("Client.ShmReads").inc()
        _record_read("shm", len(out))
        return out

    def pread_many(self, offsets, sizes):
        """Batched positioned reads: with the native fastpath on, the
        whole batch becomes ONE packed op table copied out of the
        mmapped segment GIL-free — zero per-op Python frames, one
        lease touch and one metrics update per batch instead of per
        op. Byte-identical per-op fallback on any native problem."""
        if self._transport.native_fastpath and len(offsets) > 1:
            from alluxio_tpu.client import fastpath

            if fastpath.available():
                try:
                    return self._native_pread_many(offsets, sizes)
                except fastpath.NativeExecError:
                    pass  # Client.NativeFallbacks already counted
            else:
                fastpath.note_unavailable()
        return super().pread_many(offsets, sizes)

    def _native_pread_many(self, offsets, sizes):
        from alluxio_tpu import native
        from alluxio_tpu.client import fastpath

        seg = self._seg
        self._transport.maybe_renew(self._worker, seg)
        offs = np.asarray(offsets, dtype=np.int64)
        szs = np.asarray(sizes, dtype=np.int64)
        if offs.size and int(offs.min()) < 0:
            # negative offsets hit memoryview's from-the-end slicing in
            # the per-op path; keep that quirk on the Python rung
            raise fastpath.NativeExecError("negative offset")
        # clamp exactly like ShmSegment.view: min(n, seg.length - off),
        # floored at zero (past-EOF and negative sizes read empty)
        lens = np.clip(np.minimum(szs, seg.length - offs), 0, None)
        bounds = np.zeros(offs.size + 1, dtype=np.int64)
        np.cumsum(lens, out=bounds[1:])
        dest = bytearray(int(bounds[-1]))
        if len(dest):
            mm = seg.live_map()
            loc = native._buffer_address(mm) if mm is not None else None
            if loc is None:
                raise fastpath.NativeExecError("no segment address")
            addr, n, keep = loc
            ops = fastpath.op_table(offs.size)
            ops["src"] = addr  # kind zero-init == OP_COPY
            ops["src_off"] = offs.astype(np.uint64)
            ops["src_len"] = n
            ops["dst_off"] = bounds[:-1]
            ops["len"] = lens
            fastpath.execute_table(ops, dest, host="shm")
            del keep
        from alluxio_tpu.client.block_streams import _metrics

        m = _metrics()
        m.counter("Client.ShmReads").inc(offs.size)
        m.counter("Client.BytesRead.shm").inc(len(dest))
        m.counter("Client.BlocksRead.shm").inc(offs.size)
        return fastpath.slice_out(dest, bounds.tolist())

    def memoryview(self) -> Optional[memoryview]:
        return self._seg.view()

    def numpy_view(self, dtype=np.uint8) -> np.ndarray:
        """Zero-copy ndarray over the shared pages — feed straight to
        ``jax.device_put`` (the DLPack/``np.frombuffer`` handoff)."""
        out = self._seg.export(lambda mm: np.frombuffer(mm, dtype=dtype))
        if out is None:
            return np.empty(0, dtype=dtype)
        from alluxio_tpu.metrics import metrics

        metrics().counter("Client.ShmReads").inc()
        _record_read("shm", self._seg.length)
        return out
