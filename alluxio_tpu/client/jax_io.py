"""Zero-copy JAX read path: cached blocks -> device arrays.

**The TPU-native replacement for the reference's FUSE data path**
(BASELINE.json north star: replace ``integration/fuse`` -> page cache ->
``cudaMemcpy`` with cached blocks materializing as ``jax.Array``). Ladder
per block:

1. **HBM hit** — the block is already device-resident in the HBM page
   store: the "read" returns the live ``jax.Array``; no host traffic at
   all.
2. **Host hit (same-host lease plane)** — block cached on a same-host
   worker, in any tier: lease -> mmap -> zero-copy numpy view, its pages
   made present in one kernel call -> ``jax.device_put`` (one DMA, no
   intermediate copy), then the HBM store retains it for next epoch.
3. **Cold** — worker read-through from the UFS (caching it), then (2).

``device_put`` dispatches asynchronously, so while the consumer sets
the pace the loader keeps ``prefetch`` transfers in flight behind the
block it hands out — the double-buffering that hides H2D latency behind
step time (SURVEY.md hard part: "prefetch collectives must overlap
compute"); where the producer sets it, a block is handed out as soon as
its transfer is issued.
"""

from __future__ import annotations

import contextlib
import functools
import queue
import socket
import threading
import time
import weakref
from collections import deque
from concurrent.futures import CancelledError
from typing import Iterator, List, NamedTuple, Optional, Sequence

import numpy as np

from alluxio_tpu.client.block_streams import is_local_worker
from alluxio_tpu.client.cache.evictor import NextUseCacheEvictor
from alluxio_tpu.client.cache.hbm_store import HbmPageStore, default_device
from alluxio_tpu.client.cache.meta import PageId
from alluxio_tpu.client.file_system import STATUS_BATCH_PATHS, FileSystem
from alluxio_tpu.conf import Keys
from alluxio_tpu.metrics import metrics
from alluxio_tpu.metrics.stall import (BUCKET_ADVICE, SIZE_BUCKETS,
                                       STALL_BUCKETS, size_bucket)
from alluxio_tpu.shm import ShmSegmentUnavailableError
from alluxio_tpu.utils.tracing import current_span, tracer

#: opens of one block before a segment released under it is an error
_REOPEN_TRIES = 16

#: the share of the same-host worker's top tier a windows pass may keep
#: pinned through its leases: the worker starts to free a tier past 95%
#: of it (``worker/management.py``), so below that the rest of the tier
#: stays evictable for other clients' writes and promotions
_WINDOW_PIN_SHARE = 0.95


#: live StepStats instances backing the ONE process-level
#: Client.InputBoundFraction gauge — per-instance registration would
#: let a closed loader's frozen fraction shadow the running one (and
#: pin the dead loader via the registry's closure)
_LIVE_STEP_STATS: "weakref.WeakSet" = None  # type: ignore[assignment]
_GAUGE_LOCK = threading.Lock()


def _process_input_bound_fraction() -> float:
    with _GAUGE_LOCK:
        # copy under the lock: a concurrent StepStats.__init__ add()
        # mid-iteration raises "set changed size during iteration"
        stats = list(_LIVE_STEP_STATS or ())
    if not stats:
        return 0.0
    wait = elapsed = 0.0
    for st in stats:
        w, e = st.window_totals()
        wait += w
        elapsed += e
    return (wait / elapsed) if elapsed > 0 else 0.0


class StepStats:
    """Input-stall attribution for one :class:`DeviceBlockLoader`.

    Every time the consumer waits on the loader pipeline, the wait is
    attributed to the serving tier of the block that eventually arrived.
    Exports ``Client.InputStall.<bucket>`` timers (local percentiles),
    additive ``Client.InputStallUs/Count/Bytes.<bucket>`` counters (they
    roll up to ``Cluster.*`` on the metrics heartbeat), and a rolling
    input-bound-fraction gauge — what ``fsadmin report stall``, the
    master statuspage and the stress suite read."""

    def __init__(self, window: int = 512) -> None:
        global _LIVE_STEP_STATS

        self._lock = threading.Lock()
        self._m = metrics()
        self.wait_s = {b: 0.0 for b in STALL_BUCKETS}
        self.count = {b: 0 for b in STALL_BUCKETS}
        self.bytes = {b: 0 for b in STALL_BUCKETS}
        # op-size attribution alongside the tier attribution: a stall
        # profile dominated by le4k ops is per-op RPC overhead, not
        # bandwidth — different fix, so it gets its own columns
        self.size_wait_s = {b: 0.0 for b in SIZE_BUCKETS}
        self.size_count = {b: 0 for b in SIZE_BUCKETS}
        self.size_bytes = {b: 0 for b in SIZE_BUCKETS}
        # the tier x size cross: "le4k stalls" alone doesn't say whether
        # the small reads were already on the SHM plane (compute-bound,
        # nothing to turn) or still paying remote RPCs (enable batching
        # / co-locate) — fsadmin report stall renders this split
        self.cross_wait_s = {(t, s): 0.0 for t in STALL_BUCKETS
                             for s in SIZE_BUCKETS}
        self.cross_count = {(t, s): 0 for t in STALL_BUCKETS
                            for s in SIZE_BUCKETS}
        #: rolling (wait_s, elapsed_s) per consumed block — the gauge's
        #: window, so the fraction tracks NOW, not the whole run
        self._window: deque = deque(maxlen=window)
        with _GAUGE_LOCK:
            if _LIVE_STEP_STATS is None:
                _LIVE_STEP_STATS = weakref.WeakSet()
            _LIVE_STEP_STATS.add(self)
        # one registration for the whole process (idempotent overwrite
        # of the same function): the gauge pools LIVE collectors only
        self._m.register_gauge("Client.InputBoundFraction",
                               _process_input_bound_fraction)

    def close(self) -> None:
        """Drop this collector from the process gauge (its additive
        counters keep their totals — only the live fraction stops)."""
        with _GAUGE_LOCK:
            if _LIVE_STEP_STATS is not None:
                _LIVE_STEP_STATS.discard(self)

    def window_totals(self) -> "tuple[float, float]":
        """(waited_s, elapsed_s) over the rolling window."""
        with self._lock:
            return (sum(w for w, _ in self._window),
                    sum(e for _, e in self._window))

    def record(self, bucket: str, wait_s: float, nbytes: int,
               elapsed_s: float) -> None:
        if bucket not in self.wait_s:
            bucket = "unknown"
        sb = size_bucket(nbytes)
        with self._lock:
            self.wait_s[bucket] += wait_s
            self.count[bucket] += 1
            self.bytes[bucket] += nbytes
            self.size_wait_s[sb] += wait_s
            self.size_count[sb] += 1
            self.size_bytes[sb] += nbytes
            self.cross_wait_s[(bucket, sb)] += wait_s
            self.cross_count[(bucket, sb)] += 1
            self._window.append((wait_s, max(elapsed_s, wait_s)))
        self._m.timer(f"Client.InputStall.{bucket}").update(wait_s)
        self._m.counter(f"Client.InputStallSizeUs.{sb}").inc(
            int(wait_s * 1e6))
        self._m.counter(f"Client.InputStallSizeCount.{sb}").inc()
        self._m.counter(f"Client.InputStallUs.{bucket}").inc(
            int(wait_s * 1e6))
        self._m.counter(f"Client.InputStallCount.{bucket}").inc()
        self._m.counter(f"Client.InputStallBytes.{bucket}").inc(nbytes)
        # the tier x size cross (additive, rolls up to Cluster.*):
        # fsadmin report stall cuts the le4k row by these to show
        # whether small reads ride shm / remote / ufs
        self._m.counter(f"Client.InputStallCrossUs.{bucket}.{sb}").inc(
            int(wait_s * 1e6))
        self._m.counter(
            f"Client.InputStallCrossCount.{bucket}.{sb}").inc()

    def input_bound_fraction(self) -> float:
        """Share of recent wall time the consumer spent waiting for
        input (0 = compute-bound, 1 = fully input-bound)."""
        wait, elapsed = self.window_totals()
        return (wait / elapsed) if elapsed > 0 else 0.0

    def report(self) -> dict:
        """Ranked bottleneck verdict (the input doctor)."""
        with self._lock:
            wait = dict(self.wait_s)
            count = dict(self.count)
            nbytes = dict(self.bytes)
            s_wait = dict(self.size_wait_s)
            s_count = dict(self.size_count)
            s_bytes = dict(self.size_bytes)
            x_wait = dict(self.cross_wait_s)
            x_count = dict(self.cross_count)
        total = sum(wait.values())
        buckets = {}
        for b in STALL_BUCKETS:
            if not count[b]:
                continue
            buckets[b] = {
                "wait_s": round(wait[b], 6), "count": count[b],
                "bytes": nbytes[b],
                "share": round(wait[b] / total, 4) if total else 0.0,
            }
        ranked = sorted(buckets, key=lambda b: buckets[b]["wait_s"],
                        reverse=True)
        frac = self.input_bound_fraction()
        if not ranked:
            verdict = "no input-stall samples recorded"
        else:
            top = ranked[0]
            verdict = (f"input-bound {frac:.0%} of recent wall time; "
                       f"top bottleneck: {top} "
                       f"({buckets[top]['share']:.0%} of "
                       f"{total:.3f}s stall) — {BUCKET_ADVICE[top]}")
        size_buckets = {}
        for b in SIZE_BUCKETS:
            if not s_count[b]:
                continue
            # per-size tier split: which plane the ops of this size rode
            # (the le4k row is how you read "did batching/SHM land?")
            by_source = {}
            for t in STALL_BUCKETS:
                if not x_count[(t, b)]:
                    continue
                by_source[t] = {
                    "wait_s": round(x_wait[(t, b)], 6),
                    "count": x_count[(t, b)],
                    "share": round(x_wait[(t, b)] / s_wait[b], 4)
                    if s_wait[b] else 0.0,
                }
            size_buckets[b] = {
                "wait_s": round(s_wait[b], 6), "count": s_count[b],
                "bytes": s_bytes[b],
                "share": round(s_wait[b] / total, 4) if total else 0.0,
                "by_source": by_source,
            }
        return {"total_wait_s": round(total, 6),
                "input_bound_fraction": round(frac, 4),
                "buckets": buckets, "ranked": ranked,
                "size_buckets": size_buckets,
                "verdict": verdict}


def _next_use_evictor(svc, block_of: dict) -> NextUseCacheEvictor:
    """The HBM tier's evictor under a prefetch service: a page's key is
    when the oracle's order reads its block next (``served``: after the
    access at the cursor, which is this page's own hit); a page the
    loader does not plan is never read. It holds the service and the
    loader's page -> block id map and NOT the loader: a closed loader
    must go with its last reference, not wait in a cycle for the
    collector (whose pass then lands in the next job start)."""
    return NextUseCacheEvictor(
        lambda pid, served: svc.next_use(block_of.get(pid), served))


class RowGroup(NamedTuple):
    """One row group's projection as :meth:`DeviceBlockLoader.row_groups`
    lands it: ONE pytree, so one ``device_put`` a row group. Every array
    has the pass's one row count; rows from ``num_rows`` on are
    padding."""

    #: column -> fixed-width values; a NULL and the padding land as 0
    values: dict
    #: nullable column -> bool, False at a NULL and at the padding
    valid: dict
    #: int32 scalar: the rows the row group holds
    num_rows: np.ndarray


def _land_dtype(arrow_type) -> np.dtype:
    """The dtype a fixed-width Arrow type lands as: a decimal as its
    unscaled integer, int32 up to precision 9 and int64 up to 18; a
    date, time, timestamp or duration as its integer."""
    import pyarrow as pa

    t = arrow_type
    if pa.types.is_decimal(t) and t.precision <= 18:
        return np.dtype("<i4" if t.precision <= 9 else "<i8")
    if pa.types.is_boolean(t):
        return np.dtype(bool)
    if pa.types.is_integer(t) or pa.types.is_floating(t):
        return np.dtype(t.to_pandas_dtype())
    if (pa.types.is_date(t) or pa.types.is_time(t)
            or pa.types.is_timestamp(t) or pa.types.is_duration(t)):
        return np.dtype(f"<i{t.bit_width // 8}")
    raise TypeError(f"a column of type {t} has no fixed-width landing")


def _bits(buf, offset: int, n: int) -> np.ndarray:
    """``n`` bools of an Arrow bitmap from bit ``offset`` (LSB first)."""
    return np.unpackbits(np.frombuffer(buf, np.uint8), count=offset + n,
                         bitorder="little")[offset:].view(bool)


def _chunk_values(arr, dtype: np.dtype) -> np.ndarray:
    """A view of one Arrow chunk's values as ``dtype``, no copy but a
    boolean's unpacking. A decimal's view is its values' low words
    (every fourth 32-bit word of a ``decimal128``), which two's
    complement makes the value wherever it fits."""
    import pyarrow as pa

    n, off = len(arr), arr.offset
    data = arr.buffers()[1]
    if not n or data is None:
        return np.zeros(0, dtype)
    if pa.types.is_boolean(arr.type):
        return _bits(data, off, n)
    step = arr.type.bit_width // 8 // dtype.itemsize \
        if pa.types.is_decimal(arr.type) else 1
    return np.frombuffer(data, dtype, count=(off + n) * step)[
        off * step::step]


def _pool_array(n: int, dtype) -> np.ndarray:
    """``n`` uninitialised values in Arrow's memory pool, which keeps the
    pages of a freed landing mapped for the next row group's: a fresh
    NumPy array of 8 MB is 2,048 page faults on a host whose fault maps
    one page (the decode's own arrays come from the same pool)."""
    import pyarrow as pa

    dtype = np.dtype(dtype)
    return np.frombuffer(pa.allocate_buffer(n * dtype.itemsize), dtype)


def _land_columns(table, columns: Sequence[str], rows: int):
    """``(values, valid)`` of :class:`RowGroup` from a decoded pyarrow
    ``Table``, each array ``rows`` long; a mask for every column the
    schema calls nullable, whether or not this row group holds a
    NULL, so every item has one structure."""
    values, valid = {}, {}
    for name in columns:
        field = table.schema.field(name)
        out = _pool_array(rows, _land_dtype(field.type))
        mask = _pool_array(rows, bool) if field.nullable else None
        pos = 0
        for arr in table.column(name).chunks:
            k = len(arr)
            src = _chunk_values(arr, out.dtype)
            if mask is None:
                out[pos:pos + k] = src
            else:
                bitmap = arr.buffers()[0]
                mask[pos:pos + k] = True if bitmap is None else \
                    _bits(bitmap, arr.offset, k)
                out[pos:pos + k] = 0
                np.copyto(out[pos:pos + k], src, where=mask[pos:pos + k])
            pos += k
        out[pos:] = 0
        values[name] = out
        if mask is not None:
            mask[pos:] = False
            valid[name] = mask
    return values, valid


class _ReadLane:
    """One thread of :meth:`DeviceBlockLoader.row_groups`' host reads: a
    single-thread executor, so the row groups of a file, all sent to one
    lane, are read in order on one thread (its ``PlannedFile`` is never
    shared), and that thread's stream cache, handed to the thread as it
    starts so that retiring the lane can close it."""

    def __init__(self, tls, k: int) -> None:
        from concurrent.futures import ThreadPoolExecutor

        self.streams: dict = {}

        def init() -> None:
            tls.streams = self.streams

        self.pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"loader-host-prefetch-read{k}",
            initializer=init)


class _TableFile:
    """One file of a ``row_groups`` pass: the lane that reads it (None:
    the producer thread does), its ``PlannedFile``, built by the read of
    its first row group, and ``count``, its number of row groups, which
    that read resolves as soon as it has the footer (None where the read
    stopped or failed before)."""

    __slots__ = ("path", "lane", "pfile", "count")

    def __init__(self, path: str, lane: Optional[_ReadLane]) -> None:
        from concurrent.futures import Future

        self.path = path
        self.lane = lane
        self.pfile = None
        self.count = Future()

    def submit(self, fn, *args):
        """``fn(*args)`` on the file's lane, or where there is none, on
        the caller's thread when the returned callable is called; the
        callable returns its result or raises its error."""
        if self.lane is None:
            return functools.partial(fn, *args)
        return self.lane.pool.submit(fn, *args).result


class _Overlaps:
    """The host reads of one pass running now. ``running()`` yields a
    read's record, whose ``beside`` is True once the read has run beside
    another read of the pass for any part of its time: it began while
    another ran, or another began while it ran."""

    class _Read:
        __slots__ = ("beside",)

        def __init__(self) -> None:
            self.beside = False

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._running: list = []

    @contextlib.contextmanager
    def running(self):
        me = self._Read()
        with self._lock:
            for other in self._running:
                other.beside = me.beside = True
            self._running.append(me)
        try:
            yield me
        finally:
            with self._lock:
                self._running.remove(me)


class DeviceBlockLoader:
    """Loads whole blocks of one or more files as device-resident uint8
    arrays, with an HBM retention cache and transfer prefetch."""

    def __init__(self, fs: FileSystem, paths: Sequence[str], *,
                 device=None, hbm_bytes: int = 0,
                 prefetch: Optional[int] = None, dtype=np.uint8,
                 prefetch_service=None) -> None:
        import jax

        self._jax = jax
        self._fs = fs
        self._dtype = np.dtype(dtype)
        self._device = device or default_device()
        #: page -> master block id, the oracle's name for it
        self._block_of: dict = {}
        # which page the tier gives up is decided HERE, from whether
        # the loader knows its future: with a prefetch service its
        # order is the oracle's, so the page whose next use lies
        # farthest ahead goes (on a fresh permutation every epoch what
        # was read last says nothing of what is read next); without
        # one, LRU
        self._hbm = HbmPageStore(
            hbm_bytes, self._device,
            evictor="LRU" if prefetch_service is None else
            _next_use_evictor(prefetch_service, self._block_of)) \
            if hbm_bytes > 0 else None
        if prefetch is None:
            # double-buffer depth for the zero-copy iterator
            # (atpu.tpu.prefetch.buffer.batches, default 2)
            prefetch = fs._conf.get_int(Keys.TPU_PREFETCH_BUFFER_BATCHES)
        self._prefetch = max(0, prefetch)
        # clairvoyant prefetch service (prefetch/service.py). None (the
        # default) leaves every code path byte-identical to a loader
        # without the subsystem; set, the loader consumes epochs in the
        # oracle's seeded order, registers its cursor via on_consume,
        # and records hit/late/miss outcomes.
        self._svc = prefetch_service
        self._epoch_counter = 0
        self._m = metrics()
        #: time the producer spent blocked on a FULL queue (reads 0, not
        #: "absent", for a loader whose producer never was)
        self._blocked_us = self._m.counter("Client.JaxProducerBlockedUs")
        #: items the consumer handed out, and of them those handed out
        #: with fewer than ``prefetch`` behind them because the queue
        #: held none
        self._yields = self._m.counter("Client.JaxLoaderYields")
        self._early_yields = self._m.counter("Client.JaxLoaderEarlyYields")
        # how often the kernel mapped a missed block whole, of the
        # non-empty host views the producer made present
        self._prefault_blocks = self._m.counter("Client.JaxPrefaultBlocks")
        self._prefault_populated = self._m.counter(
            "Client.JaxPrefaultPopulated")
        #: input doctor: per-tier wait attribution for this loader
        self.step_stats = StepStats()
        #: flat list of (path, block_index, page_id)
        self._plan: List[tuple] = []
        #: path -> master block ids (public: saves consumers a
        #: get_status round-trip per path, e.g. placement reporting)
        self.block_ids_by_path: dict = {}
        self._infos = {}
        #: the paths as given: ``windows``' file indices point here
        self._paths = list(paths)
        # the prefetch service already resolved these paths for its
        # manifest: reuse those FileInfos rather than paying a second
        # get_status round per file on the startup path
        resolved = dict(prefetch_service.oracle.manifest.file_infos) \
            if prefetch_service is not None else {}
        # every other path in ONE status call for the list, where a
        # call a path was 93-95% of job start
        todo = [p for p in paths if str(p) not in resolved]
        with tracer().span("atpu.loader.resolve", paths=len(todo),
                           calls=-(-len(todo) // STATUS_BATCH_PATHS)):
            resolved.update(zip(map(str, todo), fs.get_status_many(todo)))
        for path in paths:
            info = resolved[str(path)]
            self._infos[path] = info
            self.block_ids_by_path[path] = list(info.block_ids)
            for i, block_id in enumerate(info.block_ids):
                pid = PageId(f"{info.file_id:x}", i)
                self._plan.append((path, i, pid))
                self._block_of[pid] = block_id
        # streams are per-thread: FileInStream holds per-block state, so
        # concurrent host_block callers (mesh load thread pool) must not
        # share one (close()-races would silently yield empty views)
        self._tls = threading.local()
        self._all_streams: List = []
        self._streams_lock = threading.Lock()
        #: the producer thread's stream cache, published in its finally
        #: so early-exit retirement can close it from the consumer side
        self._producer_streams = None
        # ONE persistent producer thread across epochs: a fresh thread
        # per epoch would miss the thread-local stream cache and reopen
        # every stream each epoch (fd/mmap leak over a training run)
        self._producer_pool = None
        #: ``row_groups``' read lanes (:class:`_ReadLane`), kept with
        #: their threads' stream caches across the passes that end whole
        self._lanes: Optional[List[_ReadLane]] = None
        # at most one live epoch: starting a new one (or close()) cancels
        # the previous producer, else an abandoned-but-referenced
        # generator parks the single producer thread forever and
        # close()/the next epoch() deadlock behind it
        self._epoch_lock = threading.Lock()
        self._current_stop: Optional[threading.Event] = None
        self._closed = False
        # warm the native layer at construction: its first use may g++
        # -compile the .so, which must not land on the epoch hot path
        from alluxio_tpu import native as _native

        self._has_native = _native.lib() is not None
        if self._svc is not None and self._hbm is not None:
            # the agent's HBM placements ride this loader's host-read
            # path and page store (device_put dispatches async, so the
            # agent tick stays short)
            self._svc.bind_hbm(self.prefetch_into_hbm)

    def __len__(self) -> int:
        return len(self._plan)

    @property
    def plan(self) -> List[tuple]:
        """The load plan as public ``(path, block_index)`` pairs (the
        mesh data plane builds its placement from this)."""
        return [(path, i) for (path, i, _pid) in self._plan]

    def host_block(self, path: str, index: int):
        """Public host-side read of one block (zero-copy numpy view on the
        same-host lease plane, else a streamed copy): a bare open, the
        view's pages are not made present."""
        return self._host_bytes(path, index)

    # -- single block --------------------------------------------------------
    def _stream(self, path: str):
        """This thread's stream of ``path``, opened on first use with
        the ``FileInfo`` the constructor resolved."""
        streams = getattr(self._tls, "streams", None)
        if streams is None:
            streams = self._tls.streams = {}
        f = streams.get(path)
        if f is None:
            # one cached block stream per file: the loader holds a
            # stream per (thread, path) for its whole life, so a larger
            # cache would multiply worker-side block pins
            f = self._fs.open_file(path, info=self._infos.get(path),
                                   max_open_streams=1)
            with self._streams_lock:
                # closed-check INSIDE the lock: an agent thread (HBM
                # adopt) racing close() must not register a stream
                # after close() swept _all_streams — that stream would
                # leak (with its worker-side pins) for process lifetime
                if self._closed:
                    f.close()
                    raise RuntimeError("loader is closed")
                self._all_streams.append(f)
            streams[path] = f
        return f

    def _host_bytes(self, path: str, index: int):
        """Host-side view of one block: zero-copy numpy over mmap when the
        same-host lease plane serves it, else a bytes copy from the
        stream."""
        f = self._stream(path)
        # every thread of the process opens through ONE segment cache
        # (the producer, the prefetch agent's adopt thread, a mesh
        # load's pool): another thread's opens can turn its LRU over and
        # release this block's segment between its open and its view,
        # which raises, typed; the next open finds the stream stale and
        # leases the block again
        for attempt in range(_REOPEN_TRIES):
            stream = f.block_stream(index)
            view = getattr(stream, "numpy_view", None)
            if view is None:
                break
            try:
                host = view(dtype=self._dtype)
            except ShmSegmentUnavailableError:
                if attempt == _REOPEN_TRIES - 1:
                    raise
                continue
            self._m.counter("Client.JaxShortCircuitBlocks").inc()
            self._tls.last_bucket = "shm"
            return host
        self._m.counter("Client.JaxStreamedBlocks").inc()
        # striped remote reads expose their assembly buffer as a view:
        # frombuffer wraps it zero-copy, so the bytes go straight from
        # the stripe streams into device_put with no join pass
        reader = getattr(stream, "read_all_view", None)
        buf = reader() if reader is not None else stream.read_all()
        data = np.frombuffer(buf, dtype=self._dtype)
        # AFTER the read: a stale location can self-heal into a UFS
        # read-through mid-call, and only the stream knows what served
        self._tls.last_bucket = stream.source_bucket()
        return data

    def _host_present(self, path: str, index: int):
        """A missed block ready for ``device_put``: open it, then make
        the fresh mapping's pages present off the transfer's clock, in
        one kernel call for the block and not a fault a page. Returns
        ``(host view, serving bucket)``."""
        from alluxio_tpu import native

        span = tracer().span
        with span("atpu.loader.host_read"):
            # stream cache, block_stream, lease, map
            with span("atpu.loader.open_block") as sp:
                host = self._host_bytes(path, index)
                bucket = getattr(self._tls, "last_bucket", "unknown")
                if sp is not None:
                    sp.tags["bucket"] = bucket
            if host.size:
                with span("atpu.loader.prefault", bytes=host.nbytes,
                          native=self._has_native) as sp:
                    mode = native.prefault(host)
                    if mode is None:
                        host[::4096].max()
                        mode = "touch"
                    if sp is not None:
                        sp.tags["mode"] = mode
                self._prefault_blocks.inc()
                if mode != "touch":
                    self._prefault_populated.inc()
        return host, bucket

    def _hbm_hit(self, pid: PageId):
        """The HBM tier's copy of a block; None on a miss or with no
        tier."""
        lease = self._hbm.get(pid) if self._hbm is not None else None
        if lease is None:
            return None
        self._m.counter("Client.JaxHbmHits").inc()
        arr = lease.array
        # safe to unpin before returning: eviction only drops the
        # store's reference (never arr.delete()), so the array the
        # consumer holds stays valid regardless
        lease.close()
        return arr

    def _miss_to_device(self, path: str, index: int, pid: PageId):
        """A missed block put on the device and offered to the HBM tier
        (no second transfer). Returns ``(array, adopted)``."""
        host, _bucket = self._host_present(path, index)
        arr = self._jax.device_put(host, self._device)
        return arr, self._hbm is not None and self._hbm.adopt(pid, arr)

    def prefetch_into_hbm(self, ref) -> bool:
        """Prefetch-agent hook: host-read one block and adopt it into
        the HBM tier ahead of its consume (runs on the agent's adopt
        thread; per-thread streams keep it off the producer's state).
        Where the producer missed the same block meanwhile, one of the
        two transfers is dropped by the store and counted there
        (``Client.JaxHbmAdoptDuplicates``)."""
        if self._hbm is None or self._closed:
            return False
        info = self._infos.get(ref.path)
        fid = info.file_id if info is not None else ref.file_id
        pid = PageId(f"{fid:x}", ref.block_index)
        # presence, not a hit: the consume that follows counts the hit
        if self._hbm.has(pid):
            return True
        return self._miss_to_device(ref.path, ref.block_index, pid)[1]

    def load_block(self, plan_index: int):
        """One block as a device uint8 array (HBM-cached across epochs)."""
        if self._closed:
            raise RuntimeError("loader is closed")
        path, index, pid = self._plan[plan_index]
        arr = self._hbm_hit(pid)
        if arr is None:
            arr, _adopted = self._miss_to_device(path, index, pid)
        return arr

    # -- iteration -----------------------------------------------------------
    def __iter__(self) -> Iterator:
        return self.epoch()

    def _epoch_entries(self, epoch_no: int) -> List[tuple]:
        """The per-epoch load plan as ``(path, index, pid, ref)`` rows.
        Without a prefetch service the order is the static file order
        (``ref`` None, behavior identical to pre-service builds); with
        one, it is the oracle's seeded permutation for this epoch."""
        if self._svc is None:
            return [(p, i, pid, None) for (p, i, pid) in self._plan]
        entries = []
        for ref in self._svc.epoch_sequence(epoch_no):
            info = self._infos.get(ref.path)
            fid = info.file_id if info is not None else ref.file_id
            entries.append((ref.path, ref.block_index,
                            PageId(f"{fid:x}", ref.block_index), ref))
        return entries

    def epoch(self) -> Iterator:
        """Iterate all blocks as device arrays with transfer prefetch.

        Two-stage pipeline (:meth:`_pipeline`): a producer thread does
        ALL host-side work (worker RPCs, mmap setup, making the
        mapping's pages present) ahead of the consumer, which only puts
        items on the device. A block is handed out as soon as its
        transfer is issued unless the queue already holds the next one:
        up to ``prefetch`` + 1 are in flight only while the consumer
        sets the pace. On an HBM miss the one producer sets it: the
        consumer waits on it for 80% of a scan's window
        (``loader.get_wait_share``; PERF.md section 5 has the split),
        and the first block leaves after one host read, not
        ``prefetch`` + 1. The queue is bounded, and an abandoned
        generator unblocks the producer via a stop flag.

        Early consumer exit (break mid-epoch) retires the producer
        executor: the queue is drained, the producer's streams closed,
        and the ``loader-host-prefetch`` thread joined before control
        returns — nothing leaks waiting for ``close()``."""

        def begin():
            epoch_no = self._epoch_counter
            self._epoch_counter += 1
            gen = self._svc.begin_epoch(epoch_no) \
                if self._svc is not None else None
            return self._epoch_entries(epoch_no), gen

        def produce(put, stop, entries, gen):
            for (path, index, pid, ref) in entries:
                if stop.is_set():
                    return
                arr = self._hbm_hit(pid)
                if arr is not None:
                    if ref is not None:
                        out = self._svc.on_consume(
                            ref, resident_hint=True, generation=gen)
                        if out != "stale":
                            self._svc.release(ref)
                    put((pid, arr, True, "hbm", getattr(arr, "nbytes", 0)))
                    continue
                outcome = None
                if ref is not None:
                    # classify BEFORE the read (ready state decides hit
                    # vs late); the eviction pin is released only after
                    # the read holds its own block lock. The generation
                    # fences a superseded producer's last consume off
                    # the new epoch's cursor.
                    outcome = self._svc.on_consume(ref, generation=gen)
                    t0 = time.monotonic()
                host, bucket = self._host_present(path, index)
                if ref is not None:
                    if outcome != "stale":
                        # a stale (superseded-epoch) consume must NOT
                        # release: the scheduler still counts the block
                        # ready, and the pin is what keeps that true —
                        # the new epoch's own consume releases it
                        self._svc.release(ref)
                    if outcome not in ("hit", "stale"):
                        # block-ready stall: how long the consumer
                        # waited for data clairvoyance should have had
                        # resident already
                        self._svc.record_stall(time.monotonic() - t0)
                put((pid, host, False, bucket, host.nbytes))

        return self._pipeline(produce, begin)

    def windows(self, batches, *, window_bytes: int) -> Iterator:
        """Sample-grain reads: one device array of ``(rows,
        window_bytes)`` uint8 a batch of windows.

        ``batches`` is the user's sampler, iterated on the producer
        thread: each item holds ``(file_index, byte_offset)`` rows, one
        a window (``file_index`` into the loader's paths), e.g.
        nanoGPT's ``randint`` offsets or a Megatron sample index. For
        each batch the producer reads every window through the block's
        zero-copy view (the same open as :meth:`epoch`, with its
        per-thread stream cache and its retry of a released segment)
        and copies it into row ``r`` of one fresh host buffer. On the
        same-host plane the pass reserves room in the segment cache for
        the loader's distinct blocks (``ShmTransport.reserve``), as many
        as fit in ``_WINDOW_PIN_SHARE`` of the same-host worker's top
        tier (:meth:`_pin_room`: a lease pins its block there), so a
        block stays mapped and leased for the pass after its first
        window and every later window of it is a dictionary look and a
        copy; the room goes back, and the cache trims to
        ``atpu.user.shm.segment.cache.max``, however the pass ends, and
        the trim's releases are done before ``loader.close()`` returns.
        A window that crosses a block boundary is two slices, and a
        window past the end of its file (or a file index out of range)
        fails the pass, it is never a short row. Nothing is
        prefaulted: a window touches one or two of a block's pages, so
        making 32 MiB present for it would cost some 16,000 times the
        bytes it reads. The
        consumer's side is :meth:`epoch`'s (:meth:`_pipeline`): the
        bounded queue, one ``device_put`` a batch, ``StepStats``, and
        closing the generator mid-pass retires the producer.

        The HBM tier is not consulted: it keeps whole blocks, and a
        window reads about 1/16,000 of one (a loader built for windows
        takes ``hbm_bytes=0``). Off the same-host lease plane a window
        costs its block's whole streamed read, as ``load_block`` does."""
        window_bytes = int(window_bytes)
        if window_bytes <= 0:
            raise ValueError(f"window_bytes must be positive, not "
                             f"{window_bytes}")
        m = self._m
        n_batches = m.counter("Client.JaxWindowBatches")
        n_reads = m.counter("Client.JaxWindowReads")
        #: windows whose every block the segment cache held before the
        #: open (a dictionary look, no lease)
        n_mapped = m.counter("Client.JaxWindowMapped")
        #: windows that crossed a block boundary (two slices)
        n_split = m.counter("Client.JaxWindowSplit")
        shm = getattr(self._fs.store, "shm", None)
        blocks = {b for ids in self.block_ids_by_path.values() for b in ids}

        def produce(put, stop, it):
            span = tracer().span
            with shm.reserve(len(blocks), self._pin_room()) \
                    if shm is not None else contextlib.nullcontext():
                for rows in it:
                    if stop.is_set():
                        return
                    n, plan = self._window_plan(rows, window_bytes)
                    out = np.empty((n, window_bytes), np.uint8)
                    held = [True] * n
                    with span("atpu.loader.host_read", windows=n,
                              blocks=len(plan)):
                        for r, dst, path, index, lo, hi in plan:
                            if held[r] and (shm is None or not shm.holds(
                                    self.block_ids_by_path[path][index])):
                                held[r] = False
                            with span("atpu.loader.open_block"):
                                host = self._host_bytes(path, index)
                            out[r, dst:dst + hi - lo] = \
                                host.view(np.uint8)[lo:hi]
                            # no view may outlive the copy: past the
                            # pass's room the next open evicts
                            del host
                    n_batches.inc()
                    n_reads.inc(n)
                    n_mapped.inc(sum(held))
                    n_split.inc(len(plan) - n)
                    put((None, out, False,
                         getattr(self._tls, "last_bucket", "unknown"),
                         out.nbytes))

        return self._pipeline(produce, lambda: (iter(batches),))

    def row_groups(self, columns: Sequence[str], *,
                   rows: Optional[int] = None) -> Iterator:
        """A projection of the loader's Parquet files on the device, one
        :class:`RowGroup` a row group, files in the loader's order.

        A row group's host read: the footer when the pass reaches its
        file (read through the reading thread's stream of the file with
        the ``FileInfo`` the constructor resolved, and cached as
        ``read_columns`` caches it: a job start pays one), then the
        planned read that ``read_columns`` runs (``PlannedFile.fetch``:
        ``pread_ranges`` of the projection's coalesced ranges, zero-copy
        views on the same-host plane, each range made present and never
        its block; ``PlannedFile.decode``: pyarrow's decode), then the
        landing (:func:`_land_columns`): every column as ONE fixed-width
        array of ``rows`` (default: the pass's first row group's), a
        decimal as its unscaled integer (int32 up to precision 9, int64
        up to 18) read from Arrow's buffer, never a Python object;
        a nullable column with a validity mask; a shorter row group
        padded, the padding False in every mask and beyond ``num_rows``,
        so one program takes every item. A row group longer than
        ``rows`` fails the pass, as does a file the planner cannot plan
        (``ParquetPlanError``).

        Up to ``max(1, prefetch)`` row groups' host reads run at once,
        on the loader's read lanes (:class:`_ReadLane`: threads named
        ``loader-host-prefetch-read<k>``, file ``i`` on lane ``i %
        depth``, so a file's row groups are read in order on one thread
        and reads overlap across files), started at the first pass that
        needs them and kept, with their streams, across passes that end
        whole; with ``prefetch`` 0 the producer thread reads one row
        group at a time itself. The pass's first row group is read
        alone: no second read begins before its item is on the queue,
        so a cold start's first item waits for one uncontended read.
        The producer thread hands the items to the queue strictly in
        the pass's order, and a failed read fails the pass at its place
        in it, after the items before it. ``Client.JaxTableReadsOverlapped``
        counts the row groups whose read ran beside another read of the
        pass. The consumer's side is :meth:`epoch`'s (:meth:`_pipeline`):
        the bounded queue, ONE ``device_put`` a row group. Closing the
        generator mid-pass, a newer pass, a failed read and :meth:`close`
        retire the lanes (a read not begun is cancelled, one running
        ends) and close their streams. The HBM tier is not consulted: it
        keeps whole blocks, and a projection reads a share of one."""
        from alluxio_tpu.table.reader import PlannedFile

        columns = [str(c) for c in columns]
        if rows is not None and rows <= 0:
            raise ValueError(f"rows must be positive, not {rows}")
        m = self._m
        n_groups = m.counter("Client.JaxTableRowGroups")
        n_rows = m.counter("Client.JaxTableRows")
        #: the planned (coalesced) range bytes fetched
        n_fetched = m.counter("Client.JaxTableFetchedBytes")
        #: the row groups' compressed bytes, every column
        n_group_bytes = m.counter("Client.JaxTableRowGroupBytes")
        #: reads pyarrow made outside the fetched ranges
        n_misses = m.counter("Client.JaxTablePlanMisses")
        #: row groups whose host read ran beside another of the pass
        n_overlapped = m.counter("Client.JaxTableReadsOverlapped")
        conf = self._fs.conf
        depth = max(1, self._prefetch)

        def produce(put, stop):
            span = tracer().span
            overlaps = _Overlaps()

            def read(f: _TableFile, index: int, pad: Optional[int]):
                """Row group ``index`` of ``f`` read on the host:
                ``(item, pad)``, or None where the file holds no row
                group or the pass has stopped."""
                try:
                    if stop.is_set():
                        return None
                    route: dict = {}
                    with overlaps.running() as me:
                        with span("atpu.loader.host_read",
                                  row_group=index) as sp:
                            if f.pfile is None:
                                f.pfile = PlannedFile(
                                    self._stream(f.path), f.path,
                                    self._infos[f.path], columns, conf)
                                f.count.set_result(len(f.pfile.plans))
                                if not f.pfile.plans:
                                    return None
                            pfile = f.pfile
                            plan = pfile.plans[index]
                            pad = plan.num_rows if pad is None else pad
                            if plan.num_rows > pad:
                                raise ValueError(
                                    f"row group {plan.index} of {f.path} "
                                    f"holds {plan.num_rows} rows, more "
                                    f"than the pass's {pad}")
                            if sp is not None:
                                sp.tags["rows"] = str(plan.num_rows)
                            pfile.fetch(plan, route)
                            misses = pfile.src.misses
                            table = pfile.decode([plan])
                            with span("atpu.table.land", rows=pad,
                                      columns=len(columns)):
                                values, valid = _land_columns(
                                    table, columns, pad)
                            del table
                finally:
                    if not f.count.done():
                        f.count.set_result(None)
                # no read begins beside this one any more: ``beside``
                # is final
                n_overlapped.inc(me.beside)
                group = pfile.metadata.row_group(plan.index)
                n_groups.inc()
                n_rows.inc(plan.num_rows)
                n_fetched.inc(sum(n for _off, n in plan.reads))
                n_group_bytes.inc(sum(
                    group.column(c).total_compressed_size
                    for c in range(group.num_columns)))
                n_misses.inc(pfile.src.misses - misses)
                item = RowGroup(values, valid, np.int32(plan.num_rows))
                return (None, item, False,
                        "shm" if "shm" in route else
                        "remote" if route else "unknown",
                        sum(a.nbytes for a in values.values())
                        + sum(a.nbytes for a in valid.values())), pad

            lanes = self._table_lanes(depth) if depth > 1 else None
            files = enumerate(self._paths)
            f, index = None, 0  # the file being granted, its next group
            pending: deque = deque()  # granted reads, in the pass's order
            pad, granting, queued, whole = rows, True, False, False
            try:
                while not stop.is_set():
                    # one read until the pass's first item is queued
                    while granting and \
                            len(pending) < (depth if queued else 1):
                        if f is not None:
                            # its first read resolves the count with the
                            # footer; None: it failed, and raises in order
                            count = f.count.result()
                            granting = count is not None
                            if index >= (count or 0):
                                f = None
                        if f is None and granting:
                            i, path = next(files, (0, None))
                            granting = path is not None
                            if granting:
                                f, index = _TableFile(
                                    path, lanes[i % depth] if lanes
                                    else None), 0
                        if granting:
                            pending.append(f.submit(read, f, index, pad))
                            index += 1
                    if not pending:
                        whole = True
                        return
                    got = pending.popleft()()
                    if got is not None:
                        item, pad = got
                        put(item)
                        queued = True
            finally:
                if not whole:
                    # stopped or failed: every read granted ends or is
                    # cancelled, and the lanes' streams close
                    self._retire_lanes()

        return self._pipeline(produce, tuple)

    def _table_lanes(self, n: int) -> List[_ReadLane]:
        """The loader's ``n`` read lanes for ``row_groups``, started by
        the first pass that needs them."""
        with self._epoch_lock:
            if self._closed:
                raise RuntimeError("loader is closed")
            if self._lanes is None:
                self._lanes = [_ReadLane(self._tls, k) for k in range(n)]
            return self._lanes

    def _retire_lanes(self) -> None:
        """Join the read lanes, cancelling the reads not yet begun, and
        close every stream their threads opened."""
        with self._epoch_lock:
            lanes, self._lanes = self._lanes, None
        for lane in lanes or ():
            lane.pool.shutdown(wait=True, cancel_futures=True)
            self._close_streams_dict(lane.streams)

    def _pin_room(self) -> int:
        """The segments a windows pass may keep leased: a lease pins its
        block in the same-host worker's tier, so as many of the
        loader's largest block as fill ``_WINDOW_PIN_SHARE`` of those
        workers' top tiers (0 with no same-host worker)."""
        host = socket.gethostname()
        top = sum(next(iter(w.capacity_bytes_on_tiers.values()), 0)
                  for w in self._fs.store.block_master.get_worker_infos()
                  if is_local_worker(w.address, host))
        block = max((min(i.block_size_bytes or i.length, i.length)
                     for i in self._infos.values()), default=0)
        return int(_WINDOW_PIN_SHARE * top) // block if block else 0

    def _window_plan(self, rows, window_bytes: int):
        """``(windows, [(row, dst, path, block index, lo, hi)])``: the
        block slices of one batch of ``(file_index, byte_offset)`` rows,
        in order; raises before any read where a window is out of its
        file."""
        rows = np.asarray(rows, dtype=np.int64).reshape(-1, 2)
        plan = []
        for r, (fi, off) in enumerate(rows.tolist()):
            if not 0 <= fi < len(self._paths):
                raise IndexError(f"window {r}: file index {fi} is not one "
                                 f"of the loader's {len(self._paths)}")
            path = self._paths[fi]
            length = self._infos[path].length
            if off < 0 or off + window_bytes > length:
                raise ValueError(
                    f"window {r}: bytes [{off}, {off + window_bytes}) of "
                    f"{path} lie outside its {length} bytes")
            bs = self._infos[path].block_size_bytes or length
            dst = 0
            while dst < window_bytes:
                index, lo = divmod(off + dst, bs)
                take = min(window_bytes - dst, bs - lo)
                plan.append((r, dst, path, index, lo, lo + take))
                dst += take
        return len(rows), plan

    def _pipeline(self, produce, begin) -> Iterator:
        """The loader's two stages, for :meth:`epoch`, :meth:`windows`
        and :meth:`row_groups`: ``produce(put, stop, *begin())`` runs on
        the ONE producer thread and hands ``(pid, data, on_device,
        bucket, nbytes)`` items to ``put`` (the bounded queue); this
        generator is the consumer's side, which puts a host item on the
        device (and offers it to the HBM tier where ``pid`` names a
        page). ``begin`` runs under the epoch lock, once this iteration
        is the live one.

        The consumer blocks on the queue only when nothing is in
        flight. With something in flight it takes only what the queue
        already holds, up to ``prefetch`` + 1 in flight, and hands out
        the oldest as soon as the queue holds none or the depth is
        full: a producer-paced pass yields each item the moment its
        transfer is issued (the first after ONE host read), a
        consumer-paced one, whose queue is full, keeps ``prefetch``
        behind every item. Items leave in the producer's order, and a
        superseded or closed iteration raises before it would hand one
        out. ``Client.JaxLoaderYields`` counts the items handed out,
        ``Client.JaxLoaderEarlyYields`` those with fewer than
        ``prefetch`` behind them."""
        span = tracer().span
        q: queue.Queue = queue.Queue(maxsize=max(1, self._prefetch) + 1)
        stop = threading.Event()
        retire = threading.Event()
        SENTINEL = object()

        def put(item) -> None:
            self._put(q, stop, item)

        def producer(args):
            try:
                produce(put, stop, *args)
            except BaseException as e:  # noqa: BLE001 re-raised in consumer
                # a read failure must FAIL the epoch, not silently end
                # it short (a truncated epoch looks complete downstream)
                put(("__error__", e))
            finally:
                put(SENTINEL)
                # publish this thread's stream cache: if the consumer
                # retires the pool AFTER we already exited (late break),
                # it closes these post-join — retire.is_set() here alone
                # would race and leak them until loader.close()
                self._producer_streams = getattr(self._tls, "streams",
                                                 None)
                if retire.is_set():
                    self._close_streams_dict(self._producer_streams)

        with self._epoch_lock:
            if self._closed:
                # a pre-close generator first iterated after close()
                # must not silently resurrect the pool/streams
                raise RuntimeError("loader is closed")
            if self._current_stop is not None:
                self._current_stop.set()
            self._current_stop = stop
            if self._producer_pool is None:
                from concurrent.futures import ThreadPoolExecutor

                self._producer_pool = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="loader-host-prefetch")
            fut = self._producer_pool.submit(producer, begin())
        inflight: deque = deque()
        finished = False

        def superseded() -> None:
            if stop.is_set():
                # cancelled by close()/a newer epoch(): fail loudly — a
                # silently-truncated epoch looks complete downstream
                raise RuntimeError(
                    "epoch cancelled: the loader was closed or a newer "
                    "epoch() superseded this iterator")

        try:
            # input-doctor accounting: each queue wait is attributed to
            # the serving tier of the item that ends it; elapsed-since-
            # last-item bounds the rolling input-bound fraction
            last_item_t = time.monotonic()
            while True:
                item = None
                if inflight:
                    room = len(inflight) <= self._prefetch
                    if room:
                        # something is on its way to the device: take
                        # only what the queue already holds
                        try:
                            item = q.get_nowait()
                        except queue.Empty:
                            pass
                    if item is None:
                        # the queue held none (the producer sets the
                        # pace) or prefetch + 1 are in flight: hand out
                        # the oldest
                        superseded()
                        self._yields.inc()
                        if room:
                            self._early_yields.inc()
                        yield inflight.popleft()
                        continue
                    wait_t0 = now = time.monotonic()
                else:
                    # nothing in flight: block. The span covers the
                    # interval StepStats.record is given as wait_s
                    with span("atpu.loader.get_wait"):
                        wait_t0 = time.monotonic()
                        while True:
                            try:
                                item = q.get(timeout=0.5)
                                break
                            except queue.Empty:
                                superseded()
                        now = time.monotonic()
                if item is SENTINEL:
                    break
                if item[0] == "__error__":
                    raise item[1]
                pid, data, on_device, bucket, nbytes = item
                self.step_stats.record(bucket, now - wait_t0, nbytes,
                                       now - last_item_t)
                last_item_t = now
                outer = current_span()
                if outer is not None:
                    # consumer-side pipeline wait: the time this step
                    # spent blocked on the producer queue
                    outer.phase("drain", (now - wait_t0) * 1000.0)
                if on_device:
                    arr = data
                else:
                    with span("atpu.loader.h2d") as sp:
                        if sp is None:
                            arr = self._jax.device_put(data, self._device)
                        else:
                            t_put = time.perf_counter()
                            arr = self._jax.device_put(data, self._device)
                            sp.phase("device_put",
                                     (time.perf_counter() - t_put)
                                     * 1000.0)
                    if self._hbm is not None and pid is not None:
                        self._hbm.adopt(pid, arr)  # no second transfer
                inflight.append(arr)
            while inflight:
                superseded()
                self._yields.inc()
                yield inflight.popleft()
            finished = True
        finally:
            with self._epoch_lock:
                # superseded by a newer epoch() or close()?
                cancelled = self._current_stop is not stop
                closed = self._closed
            # early consumer exit (break / .close() mid-epoch) on the
            # LIVE epoch: retire the producer executor entirely — the
            # producer closes its per-thread streams on the way out and
            # the pool thread is joined below, so nothing waits for
            # loader.close() to stop leaking
            early_exit = not finished and not cancelled and not closed
            if early_exit:
                retire.set()
            stop.set()
            self._drain(q)  # unblock a producer parked on the full queue
            try:
                fut.result(timeout=5)
            except CancelledError:  # close() shut the pool first
                pass
            except TimeoutError:
                if not cancelled:
                    # a live epoch's producer is wedged (e.g. hung
                    # worker RPC): surface it, don't mask the hang
                    raise
            # one last put can land between the first drain and the
            # producer observing stop: drain again now that it exited
            self._drain(q)
            if early_exit:
                with self._epoch_lock:
                    pool = None
                    if self._current_stop is stop:
                        self._current_stop = None
                        pool, self._producer_pool = \
                            self._producer_pool, None
                if pool is not None:
                    pool.shutdown(wait=True)
                    # the producer may have finished before retire was
                    # set; its published stream cache is closed here
                    # (idempotent: the dict is cleared on first close)
                    self._close_streams_dict(
                        getattr(self, "_producer_streams", None))

    @staticmethod
    def _drain(q) -> None:
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break

    def _close_streams_dict(self, streams) -> None:
        """Close a (retiring) thread's cached block streams — they must
        not linger until loader.close(). Clears the dict in place, so a
        second call (producer-side AND consumer-side retirement paths)
        is a no-op."""
        if not streams:
            return
        victims = list(streams.values())
        streams.clear()
        with self._streams_lock:
            for f in victims:
                if f in self._all_streams:
                    self._all_streams.remove(f)
        for f in victims:
            f.close()

    def _put(self, q, stop, item) -> None:
        if stop.is_set():
            return
        if not q.full():
            # the one producer is the only thread that puts: room seen
            # is room had (no span, no counter, no clock reading)
            q.put_nowait(item)
            return
        # the producer's side of the queue: blocked on a FULL queue, so
        # the consumer sets the pace. The counter grows as the time
        # passes (every poll), not when the wait ends: a producer parked
        # for seconds while nobody asks must not credit them all to the
        # instant the next ask frees it
        t0 = time.monotonic()
        try:
            with tracer().span("atpu.loader.put_wait"):
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        return
                    except queue.Full:
                        now = time.monotonic()
                        self._blocked_us.inc(int((now - t0) * 1e6))
                        t0 = now
        finally:
            self._blocked_us.inc(int((time.monotonic() - t0) * 1e6))

    def hbm_stats(self) -> dict:
        if self._hbm is None:
            return {"hbm_bytes": 0}
        return {"hbm_bytes": self._hbm.used_bytes,
                "hbm_pages": self._hbm.page_count}

    def stall_report(self) -> dict:
        """Input-doctor verdict: ranked per-tier wait attribution for
        this loader (see :meth:`StepStats.report`)."""
        return self.step_stats.report()

    def close(self) -> None:
        self.step_stats.close()  # stop feeding the process gauge
        if self._svc is not None:
            self._svc.bind_hbm(None)  # agent must not touch a dead loader
        with self._epoch_lock:
            self._closed = True
            if self._current_stop is not None:
                self._current_stop.set()  # unblock a parked producer
                self._current_stop = None
            pool, self._producer_pool = self._producer_pool, None
        if pool is not None:
            pool.shutdown(wait=True)
        self._retire_lanes()
        shm = getattr(self._fs.store, "shm", None)
        if shm is not None:
            # every windows pass has ended with its producer: what its
            # reservation's trim handed off is released before we return
            shm.drain()
        with self._streams_lock:
            # under the lock: serializes with _host_bytes' registration
            # (an in-flight HBM adopt either lands its stream here and
            # we close it, or observes _closed and closes it itself)
            for f in self._all_streams:
                f.close()
            self._all_streams.clear()
        if self._hbm is not None:
            self._hbm.close()


@functools.lru_cache(maxsize=16)
def _record_batch_programs(record_bytes: int, batch_size: int):
    """The two jitted functions of :func:`batched_device_iterator`,
    built once a ``(record_bytes, batch_size)`` and reused by every call
    (a new pass makes no compile request; ``jit`` keys the rest on the
    block's shape). Both carry ``atpu_record_batch`` in their name, so
    a device trace's ``XLA Modules`` line finds every program the
    iterator dispatches."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    batch_bytes = batch_size * record_bytes

    @jax.jit
    def atpu_record_batch(carry, carried, block):
        """One block -> every batch slot it can fill, and the next carry.

        ``carry`` is ``(batch_size, record_bytes)`` with the ``carried``
        rows left over from earlier blocks RIGHT-aligned, so the rows of
        the pass so far end where the block's begin and slot ``j`` is
        ``batch_size`` rows of [carry rows | block rows] starting
        ``carried`` rows before row ``j * batch_size`` of the block:
        one ``dynamic_slice`` of the block's own bytes a slot, whose
        shape never depends on ``carried`` (a traced scalar).

        ``dynamic_slice`` clamps a start that would run off the end. A
        slot is whole iff ``(j + 1) * batch_size <= carried + rows``,
        which is exactly when its slice ends inside the block's records;
        only the last slot can fail that, and the host, knowing
        ``carried`` and ``rows``, never yields a slot that does."""
        rows = block.shape[0] // record_bytes
        slots = (batch_size - 1 + rows) // batch_size
        carried = carried.astype(jnp.uint32)
        flat_carry = carry.reshape(-1)
        head = jnp.concatenate(
            [flat_carry, block[:min(rows, batch_size) * record_bytes]])
        out = [lax.dynamic_slice(
            head, ((batch_size - carried) * record_bytes,), (batch_bytes,))]
        for j in range(1, slots):
            out.append(lax.dynamic_slice(
                block, ((j * batch_size - carried) * record_bytes,),
                (batch_bytes,)))
        # the last batch_size rows of [carry rows | block rows]: whatever
        # is left over afterwards is their tail, right-aligned again
        if rows >= batch_size:
            tail = block[(rows - batch_size) * record_bytes:
                         rows * record_bytes]
        else:
            tail = jnp.concatenate([flat_carry[rows * record_bytes:],
                                    block[:rows * record_bytes]])
        shape = (batch_size, record_bytes)
        return tuple(b.reshape(shape) for b in out), tail.reshape(shape)

    @functools.partial(jax.jit, static_argnames="rows")
    def atpu_record_batch_tail(carry, *, rows: int):
        """The last partial batch of a pass (``drop_remainder=False``)."""
        return carry[batch_size - rows:]

    return atpu_record_batch, atpu_record_batch_tail


def batched_device_iterator(loader: DeviceBlockLoader, *, record_bytes: int,
                            batch_size: int, drop_remainder: bool = True):
    """Group fixed-size records from block arrays into batches on device.

    One pass of ``loader.epoch()``: batches of ``(batch_size,
    record_bytes)`` uint8 in shard order. Records must not straddle
    blocks (the writer pads — same contract as TFRecord sharding); the
    padding is skipped and the rows left over at a block's end are
    carried into the next block's first batch. The last partial batch
    of the pass is dropped, or yielded short with
    ``drop_remainder=False``.

    One jitted program a block returns every batch slot the block can
    fill plus the next carry (:func:`_record_batch_programs`); the
    carried count is a traced scalar, so a pass over equal-sized shards
    compiles one program however the carry walks, and the host knows
    from arithmetic how many of the slots are whole."""
    import jax

    assemble, tail = _record_batch_programs(record_bytes, batch_size)
    m = metrics()
    n_batches = m.counter("Client.JaxRecordBatches")
    n_bytes = m.counter("Client.JaxRecordBatchBytes")
    span = tracer().span
    batch_bytes = batch_size * record_bytes
    carry, carried = None, 0
    # closing the epoch with this generator retires the loader's
    # producer at once, not when the collector finds the inner generator
    with contextlib.closing(loader.epoch()) as blocks:
        for block in blocks:
            rows = block.shape[0] // record_bytes
            if not rows:
                continue
            whole, left = divmod(carried + rows, batch_size)
            with span("atpu.loader.batch_assemble", rows=rows,
                      carry_in=carried, batches=whole):
                if carry is None:
                    # rows of an empty carry are never read into a
                    # whole slot: any bytes of the right shape do
                    carry = jax.device_put(
                        np.zeros((batch_size, record_bytes), np.uint8),
                        block.sharding)
                slots, carry = assemble(carry, np.int32(carried), block)
                n_batches.inc(whole)
                n_bytes.inc(whole * batch_bytes)
            carried = left
            yield from slots[:whole]
    if carried and not drop_remainder:
        n_batches.inc()
        n_bytes.inc(carried * record_bytes)
        yield tail(carry, rows=carried)
