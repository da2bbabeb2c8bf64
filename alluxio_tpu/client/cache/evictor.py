"""Page-cache evictors.

Re-design of ``core/client/fs/src/main/java/alluxio/client/file/cache/
evictor/{CacheEvictor,LRUCacheEvictor,LFUCacheEvictor}.java``.
"""

from __future__ import annotations

import bisect
import threading
from collections import OrderedDict
from typing import Callable, Dict, List, Optional, Tuple

from alluxio_tpu.client.cache.meta import PageId


class CacheEvictor:
    def update_on_get(self, page_id: PageId) -> None:
        raise NotImplementedError

    def update_on_put(self, page_id: PageId) -> None:
        raise NotImplementedError

    def update_on_delete(self, page_id: PageId) -> None:
        raise NotImplementedError

    def evict(self) -> Optional[PageId]:
        """The next victim (not removed; caller calls update_on_delete)."""
        raise NotImplementedError

    def evict_matching(self, pred) -> Optional[PageId]:
        """First victim IN POLICY ORDER satisfying ``pred`` (reference:
        the evictor's evictMatching shape) — lets a caller skip pages it
        cannot evict (e.g. pinned) without abandoning the policy."""
        raise NotImplementedError

    @staticmethod
    def create(kind: str) -> "CacheEvictor":
        k = kind.upper()
        if k == "LRU":
            return LRUCacheEvictor()
        if k == "LFU":
            return LFUCacheEvictor()
        raise ValueError(f"unknown evictor {kind}")


class LRUCacheEvictor(CacheEvictor):
    def __init__(self) -> None:
        self._order: "OrderedDict[PageId, None]" = OrderedDict()
        self._lock = threading.Lock()

    def update_on_get(self, page_id: PageId) -> None:
        with self._lock:
            if page_id in self._order:
                self._order.move_to_end(page_id)

    def update_on_put(self, page_id: PageId) -> None:
        with self._lock:
            self._order[page_id] = None
            self._order.move_to_end(page_id)

    def update_on_delete(self, page_id: PageId) -> None:
        with self._lock:
            self._order.pop(page_id, None)

    def evict(self) -> Optional[PageId]:
        with self._lock:
            return next(iter(self._order)) if self._order else None

    def evict_matching(self, pred) -> Optional[PageId]:
        with self._lock:
            return next((p for p in self._order if pred(p)), None)


class LFUCacheEvictor(CacheEvictor):
    def __init__(self) -> None:
        self._counts: Dict[PageId, int] = {}
        self._lock = threading.Lock()

    def update_on_get(self, page_id: PageId) -> None:
        with self._lock:
            if page_id in self._counts:
                self._counts[page_id] += 1

    def update_on_put(self, page_id: PageId) -> None:
        with self._lock:
            self._counts[page_id] = self._counts.get(page_id, 0) + 1

    def update_on_delete(self, page_id: PageId) -> None:
        with self._lock:
            self._counts.pop(page_id, None)

    def evict(self) -> Optional[PageId]:
        with self._lock:
            if not self._counts:
                return None
            return min(self._counts, key=self._counts.get)

    def evict_matching(self, pred) -> Optional[PageId]:
        with self._lock:
            cands = [p for p in self._counts if pred(p)]
            return min(cands, key=self._counts.get) if cands else None


class NextUseCacheEvictor(CacheEvictor):
    """Belady's MIN for a reader whose order is known: the victim is
    the page whose next use lies farthest ahead. Not one of
    ``create``'s kinds: a user cannot pick it, the code that knows the
    order builds it (``DeviceBlockLoader`` with a prefetch service).

    ``next_use(page_id, served)`` is that order: the sequence number of
    the reader's first access of the page that it has not consumed yet
    or, with ``served``, of the first after the one it is consuming
    now (on a hit that one is this page's own: it must not be taken for
    a future use). A number later than every access the order can name
    stands for "never".

    A page's next use is fixed from its put or hit until that use, so
    it is asked for once then and kept, in a list sorted by it: an
    eviction reads the list from its far end. A kept key is never
    trusted past its use: the nearest one is asked for again at every
    eviction, and while the reader has gone by it without a look-up (an
    epoch cut short) the page is moved to its true place.
    """

    def __init__(self, next_use: Callable[[PageId, bool], int]) -> None:
        self._next_use = next_use
        #: page -> its entry in ``_by_use``
        self._entry: Dict[PageId, Tuple[int, int, PageId]] = {}
        #: (next use, insertion number, page), nearest first; the
        #: number keeps pages that are never used again apart
        self._by_use: List[Tuple[int, int, PageId]] = []
        self._inserted = 0
        self._lock = threading.Lock()

    def _drop(self, page_id: PageId) -> None:
        entry = self._entry.pop(page_id, None)
        if entry is not None:
            del self._by_use[bisect.bisect_left(self._by_use, entry)]

    def _keep(self, page_id: PageId, key: int) -> None:
        self._drop(page_id)
        self._inserted += 1
        entry = self._entry[page_id] = (key, self._inserted, page_id)
        bisect.insort(self._by_use, entry)

    def update_on_get(self, page_id: PageId) -> None:
        with self._lock:
            if page_id in self._entry:
                self._keep(page_id, self._next_use(page_id, True))

    def update_on_put(self, page_id: PageId) -> None:
        with self._lock:
            self._keep(page_id, self._next_use(page_id, False))

    def update_on_delete(self, page_id: PageId) -> None:
        with self._lock:
            self._drop(page_id)

    def evict(self) -> Optional[PageId]:
        return self.evict_matching(lambda _page: True)

    def evict_matching(self, pred) -> Optional[PageId]:
        with self._lock:
            while self._by_use:
                key, _n, page = self._by_use[0]
                now = self._next_use(page, False)
                if now <= key:
                    # the nearest key is still ahead of the reader, so
                    # every key is
                    break
                self._keep(page, now)
            return next((page for _key, _n, page
                         in reversed(self._by_use) if pred(page)), None)
