"""``readers/span_child_share`` on hand-made intervals (no trace file:
the reader's cache on ``ctx`` is filled by hand). Arithmetic only."""

import pytest

from benchmark.harness import discover

PARENT = "atpu.loader.open_block"
LEASE, EVICT = "atpu.shm.lease", "atpu.shm.evict"


def read(spans, children=(LEASE, EVICT), window=(0.0, 10.0)):
    # spans: {name: [(start_s, duration_s), ...]}, as _host_spans keeps
    ctx = {"_host_spans": {"window": window, "spans": spans}}
    reader = discover.load_module("readers", "span_child_share")
    return reader.read(ctx, parent=PARENT, children=list(children))


def test_one_thread_reads_the_covered_share_exactly():
    # a parent of 4 s: a child of 1 s, a gap of 1.5 s, a child of 1 s,
    # 0.5 s after it
    spans = {PARENT: [(1.0, 4.0)], LEASE: [(1.0, 1.0)],
             EVICT: [(3.5, 1.0)]}
    assert read(spans) == pytest.approx(50.0)
    # a grandchild inside a listed child adds nothing: a union
    spans["atpu.shm.unmap"] = [(3.6, 0.5)]
    assert read(spans, (LEASE, EVICT, "atpu.shm.unmap")) == \
        pytest.approx(50.0)
    # a child that is not listed is not counted
    assert read(spans, (LEASE,)) == pytest.approx(25.0)


def test_two_threads_in_the_parent_read_an_upper_bound():
    # thread A opens over [1, 3) with its lease over [1, 2); thread B
    # over [2, 4) with its lease over [3, 4): each names half of its
    # own open, 2 of 4 thread-seconds. Laid together the opens cover
    # [1, 4) and the leases 2 s of it: 66.7%, never under the 50%
    spans = {PARENT: [(1.0, 2.0), (2.0, 2.0)],
             LEASE: [(1.0, 1.0), (3.0, 1.0)]}
    got = read(spans)
    assert got == pytest.approx(100.0 * 2.0 / 3.0)
    assert got >= 50.0


def test_a_child_outside_every_parent_adds_nothing():
    spans = {PARENT: [(1.0, 2.0), (6.0, 2.0)],
             LEASE: [(1.0, 1.0), (4.0, 1.0)],  # the second: outside
             EVICT: [(7.5, 1.0)]}  # half of it outside
    assert read(spans) == pytest.approx(100.0 * 1.5 / 4.0)


def test_events_are_cut_at_the_windows_edges():
    spans = {PARENT: [(8.0, 4.0)],  # 2 s of it in the window
             LEASE: [(9.0, 3.0)]}
    assert read(spans) == pytest.approx(50.0)
    # a child that lies outside the window counts nothing
    spans = {PARENT: [(8.0, 4.0)], LEASE: [(10.5, 1.0)]}
    assert read(spans) == 0.0


def test_no_parent_event_reads_none_and_no_child_reads_zero():
    assert read({LEASE: [(1.0, 1.0)]}) is None
    assert read({}) is None
    assert read({PARENT: [(1.0, 2.0)]}) == 0.0
