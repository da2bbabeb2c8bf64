"""HBM page store: cached pages resident in TPU device memory.

**TPU-native addition with no reference analogue** (the reference's top
tier is host RAM behind FUSE; SURVEY.md north star: "the tiered block store
gains an HBM tier materialized as jax.Array"). Pages are ``jax.Array``s of
uint8 living on a device; a warm get is a device-resident array — zero
host traffic, consumable by a jitted step directly.

Eviction vs JAX liveness (SURVEY.md hard part "HBM-tier eviction vs JAX
liveness"): a page handed to a consumer may be woven into an XLA
computation; deleting the backing buffer under it would be a
use-after-free. So gets return **pin leases**: the store refuses to evict a
page while leases are outstanding (refcount), mirroring the worker's
``ClientRWLock`` pin discipline. Dropping the lease (or the consumer using
``jax.Array`` copies) releases it. XLA itself keeps buffers alive while an
in-flight computation references them, so the lease only needs to cover
the window between ``get`` and dispatch.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional, TYPE_CHECKING, Union

from alluxio_tpu.client.cache.meta import PageId

if TYPE_CHECKING:  # pragma: no cover
    import jax

    from alluxio_tpu.client.cache.evictor import CacheEvictor


def default_device():
    """The device an HBM tier lands on when the caller names none: the
    first local device of JAX's default backend. A JAX that came up on
    the CPU without having been told to (``JAX_PLATFORMS`` /
    ``jax_platforms`` naming ``cpu``) looked for an accelerator and
    found none; host memory is not an HBM tier, so that is an error
    here, never a silent default. Several chips: this is chip 0 — pass
    ``device=`` to place a tier anywhere else."""
    import jax

    dev = jax.local_devices()[0]
    if dev.platform == "cpu" and \
            "cpu" not in (jax.config.jax_platforms or ""):
        raise RuntimeError(
            "no accelerator found: JAX fell back to the CPU. Pass "
            "device= explicitly, or set JAX_PLATFORMS=cpu to use host "
            "memory as the device tier on purpose")
    return dev


class DevicePageLease:
    """A pinned device page; ``array`` is the jax.Array. Close to unpin."""

    def __init__(self, store: "HbmPageStore", page_id: PageId, array) -> None:
        self._store = store
        self.page_id = page_id
        self.array = array
        self._closed = False

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._store._unpin(self.page_id)

    def __enter__(self) -> "DevicePageLease":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False


class HbmPageStore:
    """Device-memory page store with pin-lease eviction safety.

    Eviction policy is a :class:`CacheEvictor`, the SPI the host page
    cache uses (reference: ``client/file/cache/evictor/
    CacheEvictor.java``), with pinned pages skipped: the evictor
    nominates victims, the store vetoes pinned ones. ``evictor`` is a
    kind ``CacheEvictor.create`` knows (LRU unless said) or an
    instance: the store's OWNER chooses, from what it knows of its
    reads. A ``DeviceBlockLoader`` bound to a prefetch service reads in
    the oracle's order and hands in a :class:`NextUseCacheEvictor`
    (the page used farthest ahead goes); every other owner keeps LRU.
    The evictor is called under the store's lock and may take locks of
    its own (store -> evictor -> prefetch scheduler -> oracle, never
    back: nothing below calls into the store).
    """

    def __init__(self, capacity_bytes: int, device=None,
                 evictor: "Union[str, CacheEvictor]" = "LRU") -> None:
        import jax  # deferred: control-plane processes never import jax

        from alluxio_tpu.client.cache.evictor import CacheEvictor
        from alluxio_tpu.metrics import metrics

        self._jax = jax
        # what the tier does that its callers cannot count around it:
        # pages taken in, pages evicted to make room for one, adopts
        # refused, adopts of a page already held. All counted in here,
        # under the one lock, so a window never holds more evictions
        # than adopts of equal-size pages
        m = metrics()
        self._adopts = m.counter("Client.JaxHbmAdopts")
        self._evictions = m.counter("Client.JaxHbmEvictions")
        self._adopt_rejected = m.counter("Client.JaxHbmAdoptRejected")
        self._adopt_duplicates = m.counter("Client.JaxHbmAdoptDuplicates")
        self._capacity = capacity_bytes
        self._device = device or default_device()
        self._pages: Dict[PageId, "jax.Array"] = {}
        self._sizes: Dict[PageId, int] = {}
        self._pins: Dict[PageId, int] = {}
        self._used = 0
        self._lock = threading.RLock()
        self._evictor = evictor if not isinstance(evictor, str) \
            else CacheEvictor.create(evictor)

    # -- capacity -----------------------------------------------------------
    @property
    def used_bytes(self) -> int:
        with self._lock:
            return self._used

    @property
    def capacity_bytes(self) -> int:
        return self._capacity

    @property
    def page_count(self) -> int:
        with self._lock:
            return len(self._pages)

    def has(self, page_id: PageId) -> bool:
        with self._lock:
            return page_id in self._pages

    # -- put/get ------------------------------------------------------------
    def put(self, page_id: PageId, host_buffer) -> bool:
        """Transfer a host buffer (bytes / numpy view / mmap view) into
        device memory. Returns False if it cannot fit after eviction."""
        import numpy as np

        arr = np.frombuffer(host_buffer, dtype=np.uint8)
        with self._lock:
            if page_id in self._pages:
                return True
            if arr.nbytes > self._capacity:
                return False  # precheck: skip a doomed transfer
            # device_put from a zero-copy numpy view: one DMA host->HBM;
            # retention bookkeeping lives in adopt() (single code path)
            return self.adopt(page_id,
                              self._jax.device_put(arr, self._device))

    def adopt(self, page_id: PageId, device_array) -> bool:
        """Retain an ALREADY device-resident array (e.g. the loader just
        ``device_put`` it for a consumer) without a second transfer.
        Returns False when it cannot fit after eviction. A page the
        store already holds keeps its array: the one offered was a
        wasted transfer (two threads missed the same block), counted
        and dropped with the caller's reference."""
        with self._lock:
            if page_id in self._pages:
                self._adopt_duplicates.inc()
                return True
            size = device_array.nbytes
            if size > self._capacity or not self._ensure_room(size):
                self._adopt_rejected.inc()
                return False
            self._pages[page_id] = device_array
            self._sizes[page_id] = size
            self._used += size
            self._evictor.update_on_put(page_id)
            self._adopts.inc()
            return True

    def get(self, page_id: PageId) -> Optional[DevicePageLease]:
        """Warm hit: the device array itself, pinned until lease close."""
        with self._lock:
            arr = self._pages.get(page_id)
            if arr is None:
                return None
            self._pins[page_id] = self._pins.get(page_id, 0) + 1
            self._evictor.update_on_get(page_id)
            return DevicePageLease(self, page_id, arr)

    def _unpin(self, page_id: PageId) -> None:
        with self._lock:
            n = self._pins.get(page_id, 0) - 1
            if n <= 0:
                self._pins.pop(page_id, None)
            else:
                self._pins[page_id] = n

    def delete(self, page_id: PageId, force: bool = False) -> bool:
        """Evict = drop the store's reference ONLY. Never ``arr.delete()``:
        that invalidates the buffer for every holder, including a consumer
        that got this array from an earlier ``get`` — JAX frees device
        memory once the last Python reference dies, which is exactly the
        liveness contract we want."""
        with self._lock:
            if not force and self._pins.get(page_id, 0) > 0:
                return False  # pinned by a live lease
            arr = self._pages.pop(page_id, None)
            if arr is None:
                return False
            self._used -= self._sizes.pop(page_id, 0)
            self._pins.pop(page_id, None)
            self._evictor.update_on_delete(page_id)
            del arr
            return True

    def _ensure_room(self, size: int) -> bool:
        """Evict per the evictor's policy until ``size`` fits, skipping
        pinned pages (the evictor nominates the first evictable candidate
        in policy order; pinned pages are excluded by predicate)."""
        while self._used + size > self._capacity:
            victim = self._evictor.evict_matching(
                lambda p: self._pins.get(p, 0) == 0 and p in self._pages)
            if victim is None:
                # evictor view stale/empty: any unpinned page as last resort
                victim = next((pid for pid in self._pages
                               if self._pins.get(pid, 0) == 0), None)
            if victim is None:
                return False
            if self.delete(victim):
                self._evictions.inc()
        return True

    def pinned_count(self) -> int:
        with self._lock:
            return sum(1 for n in self._pins.values() if n > 0)

    def close(self) -> None:
        with self._lock:
            for pid in list(self._pages):
                self.delete(pid, force=True)
