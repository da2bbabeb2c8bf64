"""Race-detection tooling tests + real-subsystem lock-order audits
(the sanitizer-CI analogue; SURVEY §5.2)."""

import threading
import time

import pytest

from alluxio_tpu.utils.race import LockOrderAuditor, Watchdog
from alluxio_tpu.utils.tracing import (
    set_tracing_enabled, tracer,
)


class TestLockOrderAuditor:
    def test_detects_ab_ba_inversion_without_deadlocking(self):
        aud = LockOrderAuditor()
        a = aud.wrap(threading.Lock(), "A")
        b = aud.wrap(threading.Lock(), "B")

        def t1():
            with a:
                with b:
                    pass

        def t2():
            with b:
                with a:
                    pass

        # run sequentially: the auditor must flag the ORDER, not need
        # an actual deadlock schedule
        t1()
        t2()
        assert aud.inversions() == [("A", "B")]
        with pytest.raises(AssertionError, match="inversion"):
            aud.assert_clean()
        assert "A held while acquiring B" in aud.report()

    def test_consistent_order_is_clean(self):
        aud = LockOrderAuditor()
        a = aud.wrap(threading.Lock(), "A")
        b = aud.wrap(threading.Lock(), "B")
        for _ in range(3):
            with a:
                with b:
                    pass
        aud.assert_clean()

    def test_blocking_acquire_records_edge_even_while_stuck(self):
        """The edge must exist BEFORE the acquire returns: in a real
        deadlock neither thread ever succeeds, and the auditor must
        still have the evidence."""
        aud = LockOrderAuditor()
        a = aud.wrap(threading.Lock(), "A")
        b_inner = threading.Lock()
        b = aud.wrap(b_inner, "B")
        b_inner.acquire()  # B held elsewhere
        released = threading.Event()

        def t():
            with a:
                b.acquire()  # blocks until we release below
                b.release()
            released.set()

        th = threading.Thread(target=t, daemon=True)
        th.start()
        deadline = time.monotonic() + 5
        while ("A", "B") not in aud.edges:
            assert time.monotonic() < deadline, "edge never recorded"
            time.sleep(0.02)
        b_inner.release()
        assert released.wait(5)

    def test_failed_trylock_records_no_edge(self):
        """hold-A-trylock-B-backoff cannot deadlock: a FAILED
        non-blocking acquire must not create an order edge (TSAN
        exempts try-lock edges for the same reason)."""
        aud = LockOrderAuditor()
        inner_b = threading.Lock()
        a = aud.wrap(threading.Lock(), "A")
        b = aud.wrap(inner_b, "B")
        inner_b.acquire()  # someone else holds B
        with a:
            assert b.acquire(blocking=False) is False  # backs off
        inner_b.release()
        with b:
            with a:  # B->A elsewhere is fine: A->B never succeeded
                pass
        aud.assert_clean()

    def test_timed_acquire_backoff_records_no_edge(self):
        """acquire(timeout=T) that fails is a timed try-lock: no edge
        (it cannot deadlock — it always comes back)."""
        aud = LockOrderAuditor()
        b_inner = threading.Lock()
        a = aud.wrap(threading.Lock(), "A")
        b = aud.wrap(b_inner, "B")
        b_inner.acquire()
        with a:
            assert b.acquire(timeout=0.05) is False
        b_inner.release()
        with b:
            with a:
                pass
        aud.assert_clean()

    def test_reentrant_acquire_not_flagged(self):
        aud = LockOrderAuditor()
        r = aud.wrap(threading.RLock(), "R")
        with r:
            with r:
                pass
        aud.assert_clean()

    def test_instrument_attr_in_place(self):
        class Holder:
            def __init__(self):
                self._lock = threading.Lock()

        h = Holder()
        aud = LockOrderAuditor()
        aud.instrument_attr(h, "_lock", "holder")
        with h._lock:
            pass
        assert not aud.inversions()


class TestWatchdog:
    def test_fires_and_raises(self):
        import io

        buf = io.StringIO()
        with pytest.raises(TimeoutError, match="watchdog"):
            with Watchdog(0.2, stream=buf):
                time.sleep(0.6)
        assert "thread dump" in buf.getvalue()

    def test_quiet_when_fast(self):
        with Watchdog(5.0):
            pass


class TestInodeTreeLockOrder:
    def test_concurrent_namespace_ops_have_no_inversions(self, tmp_path):
        """Audit the REAL master lock stack under a concurrent
        create/list/delete workload: inode-tree RWLock vs metastore and
        block-master locks must be acquired in one global order."""
        from alluxio_tpu.minicluster import LocalCluster

        with LocalCluster(str(tmp_path), num_workers=1) as cluster:
            aud = LockOrderAuditor()
            fm = cluster.master.fs_master
            aud.instrument_attr(fm.inode_tree, "lock", "inode_tree")
            aud.instrument_attr(cluster.master.block_master, "_lock",
                                "block_master")
            fs = cluster.file_system()

            errors = []

            def worker(n):
                try:
                    for i in range(8):
                        fs.write_all(f"/race/{n}/f{i}", b"x" * 64)
                    fs.list_status("/race", recursive=True)
                    for i in range(8):
                        fs.delete(f"/race/{n}/f{i}")
                except Exception as e:  # noqa: BLE001
                    errors.append(e)

            with Watchdog(120):
                threads = [threading.Thread(target=worker, args=(n,))
                           for n in range(4)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
            assert not errors, errors
            aud.assert_clean()


class TestPauseMonitor:
    def test_observe_thresholds(self):
        from alluxio_tpu.metrics.registry import MetricsRegistry
        from alluxio_tpu.utils.pause_monitor import PauseMonitor

        reg = MetricsRegistry()
        pm = PauseMonitor(interval_s=0.5, warn_s=1.0, error_s=5.0,
                          metrics=reg)
        assert pm.observe(0.6) == 0.0  # normal drift: no pause
        assert pm.observe(2.0) == 1.5  # warn-level pause
        assert reg.counter("Process.Pauses").count == 1
        assert pm.observe(6.0) == 5.5  # severe pause
        assert reg.counter("Process.SeverePauses").count == 1
        assert pm.max_pause_s == 5.5
        assert reg.snapshot()["Process.MaxPauseSeconds"] == 5.5

    def test_gauge_present_from_construction(self):
        from alluxio_tpu.metrics.registry import MetricsRegistry
        from alluxio_tpu.utils.pause_monitor import PauseMonitor

        reg = MetricsRegistry()
        PauseMonitor(metrics=reg)
        # "healthy" must read as 0.0, not as a missing series
        assert reg.snapshot()["Process.MaxPauseSeconds"] == 0.0

    def test_thread_lifecycle_and_restart(self):
        from alluxio_tpu.metrics.registry import MetricsRegistry
        from alluxio_tpu.utils.pause_monitor import PauseMonitor

        reg = MetricsRegistry()
        pm = PauseMonitor(interval_s=0.05, warn_s=0.2, error_s=10.0,
                          metrics=reg).start()
        try:
            time.sleep(0.3)  # idle: nothing recorded
            assert reg.counter("Process.SeverePauses").count == 0
        finally:
            pm.stop()
        assert pm._thread is None
        # restart after stop must actually monitor again
        pm.start()
        assert pm._thread is not None and pm._thread.is_alive()
        pm.stop()

    def test_process_singleton(self):
        from alluxio_tpu.utils import pause_monitor as pmod

        a = pmod.ensure_process_monitor()
        b = pmod.ensure_process_monitor()
        assert a is b  # one stall = one event, however many roles


class TestTracing:
    def test_span_nesting_and_snapshot(self):
        set_tracing_enabled(True)
        try:
            tracer().clear()
            with tracer().span("outer", user="t"):
                with tracer().span("inner"):
                    pass
            spans = tracer().snapshot()
            by_name = {s["name"]: s for s in spans}
            assert by_name["inner"]["parent"] == \
                by_name["outer"]["span_id"]
            assert by_name["outer"]["tags"] == {"user": "t"}
            assert by_name["inner"]["duration_ms"] is not None
        finally:
            set_tracing_enabled(False)

    def test_disabled_records_nothing(self):
        tracer().clear()
        with tracer().span("ghost"):
            pass
        assert tracer().snapshot() == []

    def test_error_recorded(self):
        set_tracing_enabled(True)
        try:
            tracer().clear()
            with pytest.raises(ValueError):
                with tracer().span("boom"):
                    raise ValueError("nope")
            (span,) = tracer().snapshot()
            assert "ValueError" in span["error"]
        finally:
            set_tracing_enabled(False)

    def test_rpc_spans_recorded_end_to_end(self, tmp_path):
        from alluxio_tpu.conf import Keys
        from alluxio_tpu.minicluster import LocalCluster

        with LocalCluster(str(tmp_path), num_workers=1,
                          conf_overrides={Keys.TRACE_ENABLED: True}) as c:
            tracer().clear()
            fs = c.file_system()
            fs.write_all("/traced.bin", b"x")
            names = {s["name"] for s in tracer().snapshot(limit=2000)}
            assert any(n.endswith(".create_file") for n in names), names
        set_tracing_enabled(False)

    def test_span_outside_a_capture_needs_no_profiler(self):
        import jax  # noqa: F401  the device-timeline sink is live

        with tracer().span("host.only", block=3) as sp:
            pass  # must not require an active profiler
        assert sp is None  # ring off: the zero-cost contract holds
