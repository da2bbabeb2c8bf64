"""Client page-cache evictors (reference
``client/file/cache/evictor/{LRUCacheEvictor,LFUCacheEvictor}.java``):
the ordering logic deciding which page leaves the local cache."""

from __future__ import annotations

import pytest

from alluxio_tpu.client.cache.evictor import (
    CacheEvictor, NextUseCacheEvictor,
)
from alluxio_tpu.client.cache.page_store import PageId


def pid(i: int) -> PageId:
    return PageId(file_id=f"f{i}", page_index=0)


class TestLru:
    def test_oldest_untouched_evicts_first(self):
        ev = CacheEvictor.create("LRU")
        for i in range(3):
            ev.update_on_put(pid(i))
        ev.update_on_get(pid(0))  # 0 is now most-recent
        assert ev.evict() == pid(1)
        ev.update_on_delete(pid(1))
        assert ev.evict() == pid(2)

    def test_get_of_unknown_page_is_noop(self):
        ev = CacheEvictor.create("LRU")
        ev.update_on_get(pid(9))
        assert ev.evict() is None

    def test_evict_matching_respects_order_and_pred(self):
        ev = CacheEvictor.create("LRU")
        for i in range(4):
            ev.update_on_put(pid(i))
        got = ev.evict_matching(lambda p: p.file_id in ("f2", "f3"))
        assert got == pid(2)  # oldest among the matching


class TestLfu:
    def test_least_frequent_evicts_first(self):
        ev = CacheEvictor.create("LFU")
        for i in range(3):
            ev.update_on_put(pid(i))
        for _ in range(3):
            ev.update_on_get(pid(0))
        ev.update_on_get(pid(2))
        assert ev.evict() == pid(1)  # count 1 vs 4 and 2

    def test_delete_forgets_counts(self):
        ev = CacheEvictor.create("LFU")
        ev.update_on_put(pid(0))
        ev.update_on_delete(pid(0))
        assert ev.evict() is None
        ev.update_on_put(pid(0))  # re-added: count restarts at 1
        ev.update_on_put(pid(1))
        ev.update_on_get(pid(1))
        assert ev.evict() == pid(0)

    def test_evict_matching_picks_least_frequent_candidate(self):
        ev = CacheEvictor.create("LFU")
        for i in range(3):
            ev.update_on_put(pid(i))
        ev.update_on_get(pid(1))
        got = ev.evict_matching(lambda p: p.file_id in ("f1", "f2"))
        assert got == pid(2)


class Reader:
    """A reader that knows its order: ``order`` holds page numbers,
    ``cursor`` is the first access it has not consumed."""

    NEVER = 1 << 40

    def __init__(self, order):
        self.order = order
        self.cursor = 0
        self.asked = 0

    def next_use(self, page, served):
        self.asked += 1
        start = self.cursor + served
        return next((t for t in range(start, len(self.order))
                     if pid(self.order[t]) == page), self.NEVER)


class TestNextUse:
    def _evictor(self, order, held):
        reader = Reader(order)
        ev = NextUseCacheEvictor(reader.next_use)
        for i in held:
            ev.update_on_put(pid(i))
        return reader, ev

    def test_farthest_next_use_evicts_first(self):
        _reader, ev = self._evictor([2, 0, 1, 0], held=[0, 1, 2, 3])
        assert ev.evict() == pid(3)  # never read
        ev.update_on_delete(pid(3))
        assert ev.evict() == pid(1)  # read at 2; 0 at 1, 2 at 0
        ev.update_on_delete(pid(1))
        assert ev.evict() == pid(0)
        assert ev.evict() == pid(0)  # nominated, not removed

    def test_a_hit_is_keyed_by_the_access_after_the_one_served(self):
        # the look-up of a hit comes BEFORE the cursor moves: the
        # access at the cursor is this one, not a future use
        reader, ev = self._evictor([0, 1, 2, 1, 0], held=[0, 1, 2])
        ev.update_on_get(pid(0))  # served at 0: next at 4
        reader.cursor = 1
        assert ev.evict() == pid(0)
        ev.update_on_get(pid(1))  # served at 1: next at 3
        reader.cursor = 2
        ev.update_on_get(pid(2))  # served at 2: never again
        reader.cursor = 3
        assert ev.evict() == pid(2)

    def test_get_of_unknown_page_is_noop(self):
        _reader, ev = self._evictor([0, 1], held=[])
        ev.update_on_get(pid(1))
        assert ev.evict() is None

    def test_evict_matching_respects_order_and_pred(self):
        _reader, ev = self._evictor([0, 1, 2, 3], held=[0, 1, 2, 3])
        keep = ("f2", "f3")  # pinned, say
        assert ev.evict_matching(lambda p: p.file_id not in keep) == pid(1)
        assert ev.evict_matching(lambda p: False) is None
        assert ev.evict() == pid(3)  # the vetoed are still candidates

    def test_a_key_the_cursor_has_passed_is_asked_for_again(self):
        # page 0 was kept for its access at 1 and the reader went by
        # without a look-up (an epoch cut short, a placement that
        # landed in the gap): its kept key says "soonest", its true
        # next use is the farthest
        reader, ev = self._evictor([3, 0, 1, 2, 1, 2, 0], held=[0, 1, 2])
        reader.cursor = 3
        ev.update_on_get(pid(2))  # hit at 3: next at 5
        reader.cursor = 4
        assert ev.evict_matching(lambda p: p != pid(2)) == pid(0)
        assert ev.evict() == pid(0)  # and over everything: 6 > 5 > 4

    def test_a_kept_key_costs_one_question_an_eviction(self):
        reader, ev = self._evictor(list(range(64)), held=range(64))
        reader.asked = 0
        assert ev.evict() == pid(63)
        assert reader.asked == 1

    def test_delete_forgets(self):
        _reader, ev = self._evictor([0, 1], held=[0, 1, 2])
        ev.update_on_delete(pid(2))
        assert ev.evict() == pid(1)
        ev.update_on_delete(pid(1))
        ev.update_on_delete(pid(0))
        assert ev.evict() is None
        ev.update_on_delete(pid(0))  # twice is fine

    def test_one_entry_a_page_however_many_hits(self):
        order = [i % 4 for i in range(400)]
        reader, ev = self._evictor(order, held=range(4))
        for t in range(390):
            reader.cursor = t
            ev.update_on_get(pid(order[t]))
        assert len(ev._by_use) == len(ev._entry) == 4
        reader.cursor = 390  # pages 2 3 0 1 2 ...: 1 is read last
        assert ev.evict() == pid(1)

    def test_pages_never_used_again_keep_apart(self):
        _reader, ev = self._evictor([0], held=[0, 1, 2, 3])
        gone = set()
        for _ in range(3):
            victim = ev.evict()
            assert victim not in gone and victim != pid(0)
            gone.add(victim)
            ev.update_on_delete(victim)
        assert ev.evict() == pid(0)

    def test_it_is_not_a_kind_a_user_can_name(self):
        for kind in ("NEXT_USE", "NEXTUSE", "BELADY", "MIN"):
            with pytest.raises(ValueError):
                CacheEvictor.create(kind)


class TestFactory:
    def test_create_and_unknown(self):
        assert CacheEvictor.create("LRU").evict() is None
        assert CacheEvictor.create("LFU").evict() is None
        with pytest.raises(ValueError):
            CacheEvictor.create("CLOCK")
