"""DeviceBlockLoader tests on the CPU backend: epoch pipelining,
HBM-retention hits, and lifecycle edge cases (the close()/second-epoch
deadlock regression for the single-producer design)."""

import threading

import numpy as np
import pytest

from alluxio_tpu.minicluster import LocalCluster

BLOCK = 64 * 1024


@pytest.fixture()
def cluster(tmp_path):
    with LocalCluster(str(tmp_path), num_workers=1,
                      block_size=BLOCK) as c:
        yield c


def _make_loader(cluster, n_blocks=4, hbm_bytes=0, prefetch=2):
    from alluxio_tpu.client.jax_io import DeviceBlockLoader

    fs = cluster.file_system()
    data = bytes(range(256)) * (n_blocks * BLOCK // 256)
    fs.write_all("/loader/data.bin", data)
    loader = DeviceBlockLoader(fs, ["/loader/data.bin"],
                               hbm_bytes=hbm_bytes, prefetch=prefetch)
    return loader, data


class TestEpoch:
    def test_epoch_yields_all_blocks_in_order(self, cluster):
        loader, data = _make_loader(cluster)
        try:
            out = b"".join(
                np.asarray(b).tobytes() for b in loader.epoch())
            assert out == data
        finally:
            loader.close()

    def test_hbm_retention_serves_second_epoch(self, cluster):
        loader, data = _make_loader(cluster, hbm_bytes=16 << 20)
        try:
            list(loader.epoch())
            hits0 = _hbm_hits()
            out = b"".join(
                np.asarray(b).tobytes() for b in loader.epoch())
            assert out == data
            assert _hbm_hits() - hits0 >= len(loader)
        finally:
            loader.close()

    def test_load_block_single(self, cluster):
        loader, data = _make_loader(cluster)
        try:
            arr = np.asarray(loader.load_block(1))
            assert arr.tobytes() == data[BLOCK:2 * BLOCK]
        finally:
            loader.close()


    def test_a_miss_is_made_present_once_and_a_hit_never(
            self, cluster, monkeypatch):
        """``load_block`` and ``prefetch_into_hbm`` take the producer's
        miss: one ``native.prefault`` of the mapped block before its
        ``device_put``, none for a block the HBM tier already holds."""
        from alluxio_tpu import native
        from alluxio_tpu.prefetch.oracle import BlockRef

        loader, data = _make_loader(cluster, hbm_bytes=16 << 20)
        calls = []
        real = native.prefault
        monkeypatch.setattr(
            native, "prefault",
            lambda view, *a: calls.append(view.nbytes) or real(view, *a))
        ref = BlockRef("/loader/data.bin", 2, block_id=0, length=BLOCK)
        try:
            arr = np.asarray(loader.load_block(1))  # a miss
            assert arr.tobytes() == data[BLOCK:2 * BLOCK]
            assert calls == [BLOCK]
            assert loader.prefetch_into_hbm(ref)  # a miss, adopted
            assert calls == [BLOCK, BLOCK]
            hits0 = _hbm_hits()
            assert np.asarray(loader.load_block(2)).tobytes() == \
                data[2 * BLOCK:3 * BLOCK]  # the adopted block: a hit
            loader.load_block(1)
            assert loader.prefetch_into_hbm(ref)
            assert _hbm_hits() - hits0 == 2
            assert calls == [BLOCK, BLOCK]
        finally:
            loader.close()


def _hbm_hits():
    from alluxio_tpu.metrics import metrics

    return metrics().counter("Client.JaxHbmHits").count


class TestLifecycle:
    def test_close_with_live_partial_generator(self, cluster):
        """Regression: a partially-consumed epoch generator kept alive
        must not park the producer and deadlock close()."""
        loader, _ = _make_loader(cluster, n_blocks=6, prefetch=1)
        it = loader.epoch()
        next(it)  # producer is now parked on the full bounded queue
        loader.close()  # must return, not hang on pool shutdown

    def test_use_after_close_raises(self, cluster):
        """Regression: a generator created pre-close but first iterated
        post-close must not resurrect the producer pool."""
        loader, _ = _make_loader(cluster)
        stale = loader.epoch()  # generator body not started yet
        loader.close()
        with pytest.raises(RuntimeError, match="closed"):
            next(stale)
        with pytest.raises(RuntimeError, match="closed"):
            loader.load_block(0)
        assert loader._producer_pool is None  # nothing resurrected

    def test_new_epoch_cancels_stale_generator(self, cluster):
        """Regression: a second epoch() must not queue forever behind a
        producer whose abandoned-but-referenced generator never ran its
        finally block."""
        loader, data = _make_loader(cluster, n_blocks=6, prefetch=1)
        try:
            stale = loader.epoch()
            next(stale)  # keep a reference; never exhaust it
            out = b"".join(
                np.asarray(b).tobytes() for b in loader.epoch())
            assert out == data
            # the superseded iterator fails loudly, never truncates
            with pytest.raises(RuntimeError, match="cancelled"):
                list(stale)
        finally:
            loader.close()

    def test_break_mid_epoch_retires_producer(self, cluster):
        """Regression: an early consumer exit (break mid-epoch) must
        shut down the loader-host-prefetch executor and drain the
        in-flight queue — no thread may linger waiting for close()."""
        loader, data = _make_loader(cluster, n_blocks=6, prefetch=1)
        try:
            for b in loader.epoch():
                break  # generator closed here; teardown is synchronous
            assert loader._producer_pool is None
            assert not [t for t in threading.enumerate()
                        if t.name.startswith("loader-host-prefetch")]
            # the producer's cached streams went with its thread
            assert loader._all_streams == []
            # and the loader still works: a fresh epoch re-provisions
            out = b"".join(
                np.asarray(b).tobytes() for b in loader.epoch())
            assert out == data
        finally:
            loader.close()
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("loader-host-prefetch")]

    def test_generator_close_mid_epoch_retires_producer(self, cluster):
        """Same teardown contract when the consumer holds a reference
        and closes the generator explicitly."""
        loader, _ = _make_loader(cluster, n_blocks=6, prefetch=1)
        try:
            it = loader.epoch()
            next(it)  # producer is parked on the full bounded queue
            it.close()
            assert loader._producer_pool is None
            assert not [t for t in threading.enumerate()
                        if t.name.startswith("loader-host-prefetch")]
        finally:
            loader.close()

    def test_read_failure_fails_epoch(self, cluster):
        loader, _ = _make_loader(cluster)
        loader._plan.append(("/loader/does-not-exist", 0, None))
        try:
            with pytest.raises(Exception):
                list(loader.epoch())
        finally:
            loader.close()
