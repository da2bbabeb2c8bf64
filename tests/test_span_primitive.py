"""The one span primitive (``tracer().span``) and what hangs on it: the
device-timeline sink and its clock anchor, the loader's spans on both
sides of its queue, the HBM tier's and the worker tier's own counters,
the always-on per-method server timer and the worker's ``get_metrics``
pull. CPU only: nesting, counts and clocks, never a speed."""

import glob
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from alluxio_tpu.metrics import metrics
from alluxio_tpu.minicluster import LocalCluster
from alluxio_tpu.utils.tracing import (
    set_tracing_enabled, to_trace_clock, tracer,
)
from tests.testutils.loader_gates import Lockstep

BLOCK = 64 * 1024
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _count(name: str) -> float:
    return metrics().snapshot().get(name, 0)


def _capture(tmp_path, body):
    """Run ``body()`` inside a CPU profiler capture; the host events of
    the ``.xplane.pb`` as ``{name: [(start_ns, duration_ns, stats)]}``."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("atpu."):
                        events.setdefault(e.name, []).append(
                            (e.start_ns, e.duration_ns, dict(e.stats)))
    return events


@pytest.fixture()
def ring():
    set_tracing_enabled(True)
    tracer().clear()
    yield tracer()
    set_tracing_enabled(False)
    tracer().clear()


@pytest.fixture()
def cluster(tmp_path):
    with LocalCluster(str(tmp_path), num_workers=1,
                      block_size=BLOCK) as c:
        yield c


def _loader(cluster, n_blocks, **kw):
    from alluxio_tpu.client.jax_io import DeviceBlockLoader

    fs = cluster.file_system()
    data = bytes(range(256)) * (n_blocks * BLOCK // 256)
    fs.write_all("/spans/data.bin", data)
    return DeviceBlockLoader(fs, ["/spans/data.bin"], **kw)


class TestDeviceTimelineSink:
    def test_span_with_the_ring_off_lands_in_a_capture_with_its_anchor(
            self, tmp_path):
        assert not tracer().enabled
        before = time.time_ns()

        def body():
            with tracer().span("atpu.test.ring_off", block=7) as sp:
                assert sp is None  # the ring's zero-cost contract
                time.sleep(0.002)

        (ev,) = _capture(tmp_path, body)["atpu.test.ring_off"]
        _start, dur, stats = ev
        assert dur >= 2e6
        assert before <= stats["unix_ns"] <= time.time_ns()
        assert stats["block"] == 7  # entry tags ride the annotation
        assert tracer().snapshot(prefix="atpu.test.") == []

    def test_anchor_maps_a_ring_span_onto_its_trace_twin(self, tmp_path,
                                                         ring):
        def body():
            with tracer().span("atpu.test.anchor"):
                pass
            time.sleep(0.005)
            with tracer().span("atpu.test.twin"):
                time.sleep(0.001)

        events = _capture(tmp_path, body)
        a_start, _d, a_stats = events["atpu.test.anchor"][0]
        t_start, _d, t_stats = events["atpu.test.twin"][0]
        (twin,) = ring.snapshot(prefix="atpu.test.twin")
        assert twin["start_ns"] == t_stats["unix_ns"]  # one reading
        (mapped,) = to_trace_clock([twin], a_start, a_stats["unix_ns"])
        assert abs(mapped["trace_start_ns"] - t_start) < 1e6  # 1 ms
        assert mapped["span_id"] == twin["span_id"]
        # a shipped span without start_ns maps from its 3-decimal start_ms
        old = {k: v for k, v in twin.items() if k != "start_ns"}
        (mapped,) = to_trace_clock([old], a_start, a_stats["unix_ns"])
        assert abs(mapped["trace_start_ns"] - t_start) < 1e6

    def test_to_trace_clock_is_plain_offset_arithmetic(self):
        spans = [{"name": "a", "start_ns": 1_000_500, "start_ms": 1.0},
                 {"name": "b", "start_ms": 2.0}]
        out = to_trace_clock(spans, anchor_start_ns=700.0,
                             anchor_unix_ns=1_000_000)
        assert [s["trace_start_ns"] for s in out] == [1200.0, 1_000_700.0]
        assert "trace_start_ns" not in spans[0]  # copies

    def test_tracing_never_imports_jax(self):
        code = (
            "import sys\n"
            "from alluxio_tpu.utils.tracing import (\n"
            "    set_tracing_enabled, tracer)\n"
            "with tracer().span('atpu.test.off', k=1) as sp:\n"
            "    assert sp is None\n"
            "set_tracing_enabled(True)\n"
            "with tracer().span('atpu.test.on', k=1) as sp:\n"
            "    assert sp.start_ns and sp.tags == {'k': '1'}\n"
            "assert len(tracer().snapshot()) == 1\n"
            "assert 'jax' not in sys.modules, 'tracing imported jax'\n"
            "print('ok')\n")
        out = subprocess.run(
            [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
            text=True, timeout=120,
            env={**os.environ, "PYTHONPATH": ROOT})
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "ok"

    def test_an_error_leaves_both_sinks(self, tmp_path, ring):
        def body():
            with pytest.raises(ValueError):
                with tracer().span("atpu.test.boom"):
                    raise ValueError("nope")

        assert len(_capture(tmp_path, body)["atpu.test.boom"]) == 1
        (span,) = ring.snapshot(prefix="atpu.test.boom")
        assert "ValueError" in span["error"]


class TestLoaderSpans:
    def test_miss_path_spans_nest(self, cluster, ring):
        loader = _loader(cluster, 4)
        # each block put only once the one before is handed out: every
        # take of the consumer blocks on the queue
        step = Lockstep(loader)
        try:
            ring.clear()
            assert len(list(step.iterate(loader.epoch()))) == 4
            assert step.timed_out == 0
        finally:
            loader.close()
        spans = ring.snapshot(limit=4000)
        by_id = {s["span_id"]: s for s in spans}

        def parents(name):
            return [by_id[s["parent"]]["name"] if s["parent"] in by_id
                    else None for s in spans if s["name"] == name]

        assert parents("atpu.loader.open_block") == \
            ["atpu.loader.host_read"] * 4
        assert parents("atpu.loader.prefault") == \
            ["atpu.loader.host_read"] * 4
        assert parents("atpu.shm.lease") == ["atpu.loader.open_block"] * 4
        assert parents("atpu.shm.map") == ["atpu.loader.open_block"] * 4
        opened = [s for s in spans if s["name"] == "atpu.loader.open_block"]
        assert all(s["tags"]["bucket"] == "shm" for s in opened)
        # the lease and the map are child spans and nothing else: the
        # open takes no phase from them (the child carries the interval
        # on both sinks), and each lies inside the open it belongs to
        for s in opened:
            assert not s.get("phases")
            for child in ("atpu.shm.lease", "atpu.shm.map"):
                c = next(c for c in spans if c["name"] == child
                         and c["parent"] == s["span_id"])
                assert s["start_ns"] <= c["start_ns"]
                assert c["duration_ms"] <= s["duration_ms"]
        fault = next(s for s in spans
                     if s["name"] == "atpu.loader.prefault")
        assert fault["tags"]["bytes"] == str(BLOCK)
        # the consumer's side: one wait an item, and one for the end
        waits = [s for s in spans if s["name"] == "atpu.loader.get_wait"]
        assert len(waits) == 5

    def test_miss_path_spans_reach_a_capture_with_the_ring_off(
            self, cluster, tmp_path):
        loader = _loader(cluster, 3)
        step = Lockstep(loader)
        try:
            events = _capture(tmp_path / "cap",
                              lambda: list(step.iterate(loader.epoch())))
            assert step.timed_out == 0
        finally:
            loader.close()
        for name in ("atpu.loader.host_read", "atpu.loader.open_block",
                     "atpu.loader.prefault", "atpu.shm.lease",
                     "atpu.shm.map", "atpu.loader.h2d"):
            assert len(events[name]) == 3, name
        assert len(events["atpu.loader.get_wait"]) == 4
        for (rs, rd, _), (os_, od, _), (fs_, fd, st) in zip(
                sorted(events["atpu.loader.host_read"]),
                sorted(events["atpu.loader.open_block"]),
                sorted(events["atpu.loader.prefault"])):
            assert rs <= os_ and os_ + od <= fs_ and fs_ + fd <= rs + rd
            assert st["bytes"] == BLOCK

    def test_a_miss_only_epoch_counts_and_tags_every_prefault(
            self, cluster, ring):
        from alluxio_tpu import native

        blocks0 = _count("Client.JaxPrefaultBlocks")
        whole0 = _count("Client.JaxPrefaultPopulated")
        loader = _loader(cluster, 5)
        try:
            assert len(list(loader.epoch())) == 5
        finally:
            loader.close()
        modes = [s["tags"]["mode"] for s in ring.snapshot(limit=4000)
                 if s["name"] == "atpu.loader.prefault"]
        assert len(modes) == 5
        assert _count("Client.JaxPrefaultBlocks") - blocks0 == 5
        # one kernel: every block takes the same rung, and the counter
        # of blocks the kernel mapped whole says how many did not touch
        expected = native.prefault(np.zeros(BLOCK, np.uint8)) or "touch"
        assert modes == [expected] * 5
        assert _count("Client.JaxPrefaultPopulated") - whole0 == \
            (5 if expected != "touch" else 0)

    @pytest.mark.parametrize("slow,blocked", [("consumer", True),
                                              ("producer", False)])
    def test_producer_blocked_time_says_which_side_paces(
            self, cluster, slow, blocked):
        loader = _loader(cluster, 8, prefetch=1)
        if slow == "producer":
            host_bytes = loader._host_bytes

            def slowly(path, index):
                time.sleep(0.02)
                return host_bytes(path, index)

            loader._host_bytes = slowly
        before = _count("Client.JaxProducerBlockedUs")
        try:
            for _block in loader.epoch():
                if slow == "consumer":
                    time.sleep(0.02)
        finally:
            loader.close()
        grew = _count("Client.JaxProducerBlockedUs") - before
        if blocked:
            # 8 blocks at 20 ms through a queue of 2: blocked most of it
            assert grew > 60_000
        else:
            assert grew < 5_000

    def test_blocked_time_grows_while_the_producer_is_parked(self, cluster):
        # nobody asks for 0.4 s: the parked producer's time is counted
        # as it passes, not credited whole to the ask that frees it
        loader = _loader(cluster, 8, prefetch=1)
        try:
            it = loader.epoch()
            next(it)
            before = _count("Client.JaxProducerBlockedUs")
            time.sleep(0.45)
            parked = _count("Client.JaxProducerBlockedUs") - before
            assert parked >= 200_000
            next(it)
            freed = _count("Client.JaxProducerBlockedUs") - before - parked
            assert freed < 150_000  # one poll's worth at most
            it.close()
        finally:
            loader.close()

    def test_hbm_tier_counts_its_evictions_and_refusals(self, cluster):
        from alluxio_tpu.client.cache.meta import PageId

        ad0 = _count("Client.JaxHbmAdopts")
        ev0 = _count("Client.JaxHbmEvictions")
        rej0 = _count("Client.JaxHbmAdoptRejected")
        loader = _loader(cluster, 6, hbm_bytes=2 * BLOCK)
        try:
            blocks = []
            for block in loader.epoch():
                blocks.append(block)
                # counted inside the store: never more evictions than
                # adopts, wherever a window is cut
                assert _count("Client.JaxHbmEvictions") - ev0 <= \
                    _count("Client.JaxHbmAdopts") - ad0
            # a scan through a two-block tier: six pages taken in,
            # blocks 3..6 evict one each
            assert _count("Client.JaxHbmAdopts") - ad0 == 6
            assert _count("Client.JaxHbmEvictions") - ev0 == 4
            assert _count("Client.JaxHbmAdoptRejected") - rej0 == 0
            store = loader._hbm
            big = np.zeros(3 * BLOCK, np.uint8)
            import jax

            assert not store.adopt(PageId("big", 0), jax.device_put(big))
            # every page pinned: nothing to evict, the adopt is refused
            leases = [store.get(pid) for pid in list(store._pages)]
            assert not store.adopt(PageId("x", 0), blocks[0])
            for lease in leases:
                lease.close()
            assert _count("Client.JaxHbmAdoptRejected") - rej0 == 2
            assert _count("Client.JaxHbmEvictions") - ev0 == 4
            assert _count("Client.JaxHbmAdopts") - ad0 == 6
        finally:
            loader.close()


SEGMENTS = 2


@pytest.fixture()
def small_cache_cluster(tmp_path):
    # a segment cache of 2, so a scan of a few blocks turns it over as
    # the full-size scan turns over its 64
    from alluxio_tpu.conf import Keys

    with LocalCluster(str(tmp_path), num_workers=1, block_size=BLOCK,
                      conf_overrides={
                          Keys.USER_SHM_SEGMENT_CACHE_MAX: SEGMENTS}) as c:
        yield c


def _scan_loader(cluster, n_files, blocks_each):
    from alluxio_tpu.client.jax_io import DeviceBlockLoader

    fs = cluster.file_system()
    paths = [f"/spans/scan-{i}.bin" for i in range(n_files)]
    for i, path in enumerate(paths):
        fs.write_all(path, bytes([i + 1]) * (blocks_each * BLOCK))
    return DeviceBlockLoader(fs, paths), fs


class TestOpenBlockSpans:
    """What an open does beyond the lease and the map, by name: the
    victim mapping's eviction (a hand-off on the opener's thread; its
    unmap and its lease given back on the transport's own), and a
    file's block list from the master."""

    FILES, BLOCKS_EACH = 3, 3

    def _scan(self, cluster, ring):
        loader, fs = _scan_loader(cluster, self.FILES, self.BLOCKS_EACH)
        try:
            ring.clear()
            n = len(list(loader.epoch()))
            # the releaser thread's spans are in the ring once it is idle
            assert fs.store.shm.drain(10.0)
        finally:
            loader.close()
        assert n == self.FILES * self.BLOCKS_EACH
        return n, ring.snapshot(limit=4000)

    def test_a_scan_past_the_segment_cache_evicts_one_mapping_a_miss(
            self, small_cache_cluster, ring):
        n, spans = self._scan(small_cache_cluster, ring)
        by_id = {s["span_id"]: s for s in spans}
        evicts = [s for s in spans if s["name"] == "atpu.shm.evict"]
        # none while the cache fills, then one a miss
        assert len(evicts) == n - SEGMENTS
        opens = set()
        for ev in evicts:
            assert ev["tags"] == {"reason": "lru"}
            assert by_id[ev["parent"]]["name"] == "atpu.loader.open_block"
            opens.add(ev["parent"])
            # the hand-off alone: the victim's halves are not under it
            assert not [c for c in spans if c["parent"] == ev["span_id"]]
            # room is made first: the victim goes BEFORE the new
            # block's lease and map
            after = [c["name"] for c in spans
                     if c["parent"] == ev["parent"]
                     and c["start_ns"] >= ev["start_ns"]
                     and c["span_id"] != ev["span_id"]]
            assert "atpu.shm.lease" in after and "atpu.shm.map" in after
        assert len(opens) == len(evicts)  # one an open, never two
        # the halves run on the transport's own thread, under no open:
        # one unmap and one release a victim, each pair in that order
        unmaps = sorted((s for s in spans if s["name"] == "atpu.shm.unmap"),
                        key=lambda s: s["start_ns"])
        gives = sorted((s for s in spans if s["name"] == "atpu.shm.release"),
                       key=lambda s: s["start_ns"])
        assert len(unmaps) == len(gives) == len(evicts)
        for ev, un, give in zip(sorted(evicts, key=lambda s: s["start_ns"]),
                                unmaps, gives):
            assert un["tags"] == {"bytes": str(BLOCK)}
            assert un["parent"] not in by_id and give["parent"] not in by_id
            assert ev["start_ns"] <= un["start_ns"] <= give["start_ns"]

    def test_a_files_block_list_is_asked_for_once_a_file(
            self, small_cache_cluster, ring):
        _n, spans = self._scan(small_cache_cluster, ring)
        by_id = {s["span_id"]: s for s in spans}
        asked = [s for s in spans if s["name"] == "atpu.fs.block_infos"]
        assert len(asked) == self.FILES
        assert {by_id[s["parent"]]["name"] for s in asked} == {
            "atpu.loader.open_block"}
        # the file's FIRST open, before that block's lease
        for s in asked:
            lease = min((c for c in spans if c["name"] == "atpu.shm.lease"
                         and c["parent"] == s["parent"]),
                        key=lambda c: c["start_ns"])
            assert s["start_ns"] <= lease["start_ns"]

    def test_the_eviction_spans_reach_a_capture_with_the_ring_off(
            self, small_cache_cluster, tmp_path):
        loader, fs = _scan_loader(small_cache_cluster, self.FILES,
                                  self.BLOCKS_EACH)

        def body():
            list(loader.epoch())
            assert fs.store.shm.drain(10.0)  # inside the capture

        try:
            events = _capture(tmp_path / "cap", body)
        finally:
            loader.close()
        n = self.FILES * self.BLOCKS_EACH
        for name in ("atpu.shm.evict", "atpu.shm.unmap",
                     "atpu.shm.release"):
            assert len(events[name]) == n - SEGMENTS, name
        assert len(events["atpu.fs.block_infos"]) == self.FILES
        assert all(st["reason"] == "lru"
                   for _s, _d, st in events["atpu.shm.evict"])
        assert all(st["bytes"] == BLOCK
                   for _s, _d, st in events["atpu.shm.unmap"])
        # every eviction inside one open; its halves after the hand-off
        # began, in order, on another thread (so not held inside it)
        opens = sorted(events["atpu.loader.open_block"])
        for (es, ed, _), (us, ud, _), (rs, _rd, _) in zip(
                sorted(events["atpu.shm.evict"]),
                sorted(events["atpu.shm.unmap"]),
                sorted(events["atpu.shm.release"])):
            assert any(s <= es and es + ed <= s + d for s, d, _ in opens)
            assert es <= us and us + ud <= rs

    def test_a_cold_start_starts_no_release_thread(self, cluster):
        """A job start opens a handful of blocks and evicts nothing: it
        must not pay for the transport's thread, and a scan that did
        start one leaves none behind its ``close``."""
        import threading

        from alluxio_tpu.client.jax_io import DeviceBlockLoader

        def releasers():
            return {t for t in threading.enumerate()
                    if t.name == "atpu-shm-release" and t.is_alive()}

        before = releasers()
        fs = cluster.file_system()
        fs.write_all("/spans/cold.bin", b"\x05" * (4 * BLOCK))
        loader = DeviceBlockLoader(fs, ["/spans/cold.bin"])
        try:
            first = next(iter(loader.epoch()))
            first.block_until_ready()
            assert int(first[0]) == 5
            assert releasers() == before
            assert fs.store.shm._releaser is None
        finally:
            loader.close()
            fs.close()
        assert releasers() == before

    def test_an_unmap_under_a_live_view_is_counted_and_left_to_the_collector(
            self, cluster):
        fs = cluster.file_system()
        fs.write_all("/spans/held.bin", b"\x07" * BLOCK)
        try:
            with fs.open_file("/spans/held.bin") as f:
                stream = f.block_stream(0)
                assert stream.last_source == "SHM"
                held = stream.numpy_view()
                seg = stream._seg
                deferred = _count("Client.ShmUnmapDeferred")
                seg.close_map()  # raises nothing
                assert _count("Client.ShmUnmapDeferred") - deferred == 1
                assert seg.released and seg.dead
                # the pages outlive the segment for whoever holds them
                assert int(held[0]) == 7 and int(held[-1]) == 7
                del held
                # with no view alive the mapping closes at once: no count
                with fs.open_file("/spans/held.bin") as g:
                    again = g.block_stream(0)._seg
                    again.close_map()
                assert _count("Client.ShmUnmapDeferred") - deferred == 1
        finally:
            fs.close()

    def test_the_lease_and_the_map_are_spans_and_no_phase(self):
        from alluxio_tpu.stress.smallread_bench import run_shm
        from alluxio_tpu.utils.tracing import PHASES

        assert "lease_wait" not in PHASES and "shm_map" not in PHASES
        # the one reader the two phases had reads the child spans now
        out = run_shm(file_mb=1, ops=8).metrics
        assert out["zerocopy_ok"] and out["wire_serialize_ms"] == 0
        assert sorted(out["setup_spans_ms"]) == [
            "atpu.shm.lease", "atpu.shm.map"]
        assert all(ms > 0 for ms in out["setup_spans_ms"].values())
        assert "setup_phases" not in out


class TestRoleTimersAndPull:
    def test_grpc_dispatch_times_every_unary_call_with_the_ring_off(
            self, cluster):
        from alluxio_tpu.rpc.clients import WorkerClient

        assert not tracer().enabled
        worker = WorkerClient(f"localhost:{cluster.workers[0].port}")
        name = "Worker.RpcServeTime.session_heartbeat.count"
        before = _count(name)
        worker._call("session_heartbeat", {})
        assert _count(name) - before == 1
        assert _count("Worker.RpcServeTime.session_heartbeat.p50") > 0

    def test_a_failed_call_is_timed_too(self, cluster):
        from alluxio_tpu.rpc.clients import WorkerClient
        from alluxio_tpu.utils.exceptions import AlluxioTpuError

        worker = WorkerClient(f"localhost:{cluster.workers[0].port}",
                              retry_duration_s=0.0)
        name = "Worker.RpcServeTime.shm_renew.count"
        before = _count(name)
        with pytest.raises((AlluxioTpuError, KeyError)):
            worker._call("shm_renew", {})  # no session_id: the handler raises
        assert _count(name) - before == 1

    def test_fastpath_dispatch_uses_the_same_timer(self, tmp_path):
        from alluxio_tpu.rpc.core import ServiceDefinition
        from alluxio_tpu.rpc.fastpath import FastPathChannel, FastPathServer

        svc = ServiceDefinition("atpu.FileSystemMaster")
        svc.unary("probe_fast_timer", lambda r: {"got": r})
        server = FastPathServer(str(tmp_path / "fp.sock"))
        server.add_service(svc)
        server.start()
        try:
            name = "Master.RpcServeTime.probe_fast_timer.count"
            before = _count(name)
            ch = FastPathChannel(str(tmp_path / "fp.sock"))
            assert ch.call("atpu.FileSystemMaster", "probe_fast_timer",
                           {"a": 1})["got"] == {"a": 1}
            assert _count(name) - before == 1
        finally:
            server.stop()

    def test_worker_get_metrics_serves_its_own_registry(self, cluster):
        from alluxio_tpu.rpc.clients import WorkerClient

        fs = cluster.file_system()
        fs.write_all("/pull.bin", b"x" * BLOCK)
        worker = WorkerClient(f"localhost:{cluster.workers[0].port}")
        snap = worker.get_metrics()
        assert snap["Worker.BlocksCommitted"] >= 1
        # what the tier displaced reads 0, not "absent"
        assert "Worker.BlocksEvicted" in snap
        assert "Worker.BlocksDemoted" in snap
        # the pull itself is a timed unary call
        assert worker.get_metrics()[
            "Worker.RpcServeTime.get_metrics.count"] >= 1


def test_timer_histogram_is_unchanged_on_recorded_samples():
    """``update`` touches one slot and ``histogram`` accumulates on
    read: the cumulative counts are what walking every bound gave."""
    from alluxio_tpu.metrics.registry import Timer

    bounds = Timer.HISTOGRAM_BUCKETS
    samples = [0.0, 0.0003, 0.005, 0.0050001, 0.03, 0.25, 0.26, 1.0,
               9.99, 10.0, 11.0, 3600.0]
    timer = Timer()
    for s in samples:
        timer.update(s, exemplar="t" if s == 0.03 else None)
    counts, total, n = timer.histogram()
    assert counts == [sum(1 for s in samples if s <= le) for le in bounds] \
        + [len(samples)]
    assert n == len(samples) and total == pytest.approx(sum(samples))
    # the exemplar hangs on the first bound that holds its sample
    assert list(timer.exemplars()) == [bounds.index(0.05)]
    assert Timer().histogram() == ([0] * (len(bounds) + 1), 0.0, 0)


def test_one_primitive_is_all_there_is():
    import alluxio_tpu.utils.tracing as tracing

    assert not hasattr(tracing, "annotate")
    assert not hasattr(tracing, "device_trace")


def test_demotion_is_counted_apart_from_eviction(tmp_path):
    from tests.test_tiered_store import KB, make_store, put_block

    store = make_store(tmp_path, mem_cap=2 * KB, ssd_cap=100 * KB)
    demoted0 = _count("Worker.BlocksDemoted")
    evicted0 = _count("Worker.BlocksEvicted")
    for bid in (1, 2, 3):
        put_block(store, bid, bytes([bid]) * KB, tier="MEM")
    assert 1 in store.block_report()["SSD"]
    assert _count("Worker.BlocksDemoted") - demoted0 == 1
    assert _count("Worker.BlocksEvicted") - evicted0 == 0
