"""Sample-grain reads through the loader a user already builds:
``DeviceBlockLoader.windows`` against a plain NumPy reference, on a real
minicluster and the CPU.

The reference knows nothing of ``alluxio_tpu``: file ``i`` is a seeded
random byte string, and window ``(i, off)`` is ``data[i][off:off + W]``,
as nanoGPT's ``get_batch`` slices its token array."""

import threading

import numpy as np
import pytest

from alluxio_tpu.client.file_system import FileSystem
from alluxio_tpu.client.jax_io import DeviceBlockLoader
from alluxio_tpu.conf import Keys
from alluxio_tpu.metrics import metrics
from alluxio_tpu.minicluster import LocalCluster
from alluxio_tpu.utils.tracing import set_tracing_enabled, tracer
from tests.testutils.loader_gates import Lockstep, held_after_first

BLOCK = 64 << 10
#: one nanoGPT window of 1,025 uint16 tokens
WINDOW = 2050
SEED = 2**31 + 40
COUNTERS = ("Client.JaxWindowBatches", "Client.JaxWindowReads",
            "Client.JaxWindowMapped", "Client.JaxWindowSplit")


def _count(name: str) -> float:
    return metrics().snapshot().get(name, 0)


def _counts() -> dict:
    return {name: _count(name) for name in COUNTERS}


# -- the plain reference ----------------------------------------------------
def reference_files(n_files: int, file_bytes: int) -> list:
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, 256, size=file_bytes, dtype=np.uint8)
            for _ in range(n_files)]


def reference_batch(files, rows) -> np.ndarray:
    return np.stack([files[i][off:off + WINDOW] for i, off in rows])


def uniform_batches(files, n_batches: int, rows: int, seed: int = 7):
    """nanoGPT's draw: a file, then a uniform offset a window fits at."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_batches):
        fi = rng.integers(0, len(files), size=rows)
        off = [int(rng.integers(0, files[i].size - WINDOW + 1)) for i in fi]
        out.append(np.stack([fi, off], axis=1))
    return out


def crosses(files, rows) -> int:
    """Windows of ``rows`` that cross a block boundary."""
    return sum(off // BLOCK != (off + WINDOW - 1) // BLOCK
               for _i, off in rows)


def first_touches(batches) -> int:
    """Windows that read a block no earlier window read: a fresh
    client leases it, so such a window is never a dictionary look."""
    seen, n = set(), 0
    for rows in batches:
        for i, off in rows:
            blocks = {(int(i), int(off) // BLOCK),
                      (int(i), (int(off) + WINDOW - 1) // BLOCK)}
            n += bool(blocks - seen)
            seen |= blocks
    return n


def _served(method: str) -> float:
    return sum(_count(f"Worker.RpcServed.{route}.{method}")
               for route in ("fastpath", "grpc"))


# -- the deployment, tiny ---------------------------------------------------
def _cluster(tmp_path, tier=None, levels=None) -> LocalCluster:
    """One worker; ``tier`` = its top tier in blocks (64 MiB else): a
    windows pass keeps at most 95% of it leased, so a tier of 3 turns
    the pass's segments over at 2. The tier below takes what the top
    one cannot hold, unless ``levels`` is 1."""
    return LocalCluster(
        str(tmp_path), num_workers=1, block_size=BLOCK,
        worker_mem_bytes=64 << 20 if tier is None else tier * BLOCK,
        conf_overrides=None if levels is None else
        {Keys.WORKER_TIERED_STORE_LEVELS: levels})


@pytest.fixture()
def cluster(tmp_path):
    with _cluster(tmp_path) as c:
        yield c


def _write(cluster, n_files: int, blocks_each: int,
           write_type: str = "MUST_CACHE"):
    fs = cluster.file_system()
    files = reference_files(n_files, blocks_each * BLOCK)
    paths = [f"/windows/shard-{i:03d}" for i in range(n_files)]
    for path, data in zip(paths, files):
        fs.write_all(path, data.tobytes(), write_type=write_type)
    return paths, files


def _client(cluster, segments=None) -> FileSystem:
    conf = cluster.conf.copy()
    if segments is not None:
        conf.set(Keys.USER_SHM_SEGMENT_CACHE_MAX, segments)
    return FileSystem(cluster.master.address, conf=conf)


#: (files, blocks a file, segment cache, top tier in blocks, batches,
#: rows a batch)
CASES = {
    "one-block-files": (6, 1, None, None, 4, 12),
    "multi-block-files": (2, 4, None, None, 4, 12),
    "segment-cache-of-2": (6, 1, 2, 3, 6, 12),
    "multi-block-cache-of-2": (3, 3, 2, 3, 6, 8),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_windows_are_the_reference_byte_for_byte(tmp_path, case):
    n_files, blocks_each, segments, tier, n_batches, rows = CASES[case]
    with _cluster(tmp_path, tier) as cluster:
        paths, files = _write(cluster, n_files, blocks_each)
        batches = uniform_batches(files, n_batches, rows)
        if blocks_each > 1:
            # and at least one window across every boundary of file 0
            edge = [[0, b * BLOCK - WINDOW // 2]
                    for b in range(1, blocks_each)]
            batches.append(np.array(edge, dtype=np.int64))
        client = _client(cluster, segments)
        loader = DeviceBlockLoader(client, paths)
        before = _counts()
        yields0 = _count("Client.JaxLoaderYields")
        try:
            got = [np.asarray(b) for b in
                   loader.windows(batches, window_bytes=WINDOW)]
            assert client.store.shm.drain(10.0)
            if segments is not None:
                # the pass is over: the cache is back within its bound
                assert client.store.shm.cached_blocks() <= segments
        finally:
            loader.close()
            client.close()
    assert len(got) == len(batches)
    for rows_, batch in zip(batches, got):
        assert batch.dtype == np.uint8 and batch.shape == (len(rows_),
                                                            WINDOW)
        assert np.array_equal(batch, reference_batch(files, rows_))
    assert _count("Client.JaxLoaderYields") - yields0 == len(batches)
    delta = {k: _count(k) - v for k, v in before.items()}
    n_windows = sum(len(b) for b in batches)
    assert delta["Client.JaxWindowBatches"] == len(batches)
    assert delta["Client.JaxWindowReads"] == n_windows
    assert delta["Client.JaxWindowSplit"] == sum(
        crosses(files, b) for b in batches)
    if blocks_each > 1:
        assert delta["Client.JaxWindowSplit"] >= blocks_each - 1
    # the first window of every block is leased, never a dictionary look
    fresh = n_windows - first_touches(batches)
    if tier is None:  # the pass holds every block: the rest are
        assert delta["Client.JaxWindowMapped"] == fresh
    else:
        # a tier of 3 keeps 2 of more blocks than that leased: most
        # windows turn the cache over, and a revisit of a block that
        # went finds its cached stream stale
        assert delta["Client.JaxWindowMapped"] < min(fresh, n_windows / 2)


def _granted() -> float:
    return _count("Worker.ShmLeasesGranted")


def _reserved(shm) -> int:
    return sum(blocks for blocks, _room in shm._reservations)


def test_a_pass_keeps_every_block_after_its_first_touch(cluster):
    """A cache of 2 under 6 one-block files: the pass reserves room for
    all 6, so every window after a block's first is a dictionary look,
    and every block is leased once; when the loader closes the cache is
    back within 2 and every other lease has gone back."""
    paths, files = _write(cluster, 6, 1)
    batches = uniform_batches(files, 6, 12)
    client = _client(cluster, 2)
    loader = DeviceBlockLoader(client, paths)
    mapped, reads = (_count("Client.JaxWindowMapped"),
                     _count("Client.JaxWindowReads"))
    leased, released = _granted(), _served("shm_release")
    shm = client.store.shm
    reserved = []

    def sampler():  # iterated on the producer thread, inside the pass
        for rows in batches:
            reserved.append(_reserved(shm))
            yield rows

    try:
        got = [np.asarray(b) for b in
               loader.windows(sampler(), window_bytes=WINDOW)]
        assert reserved == [6] * len(batches)
        loader.close()
        assert shm.cached_blocks() <= 2 and _reserved(shm) == 0
        assert shm.drain(0.0)
        leased = _granted() - leased
        released = _served("shm_release") - released
    finally:
        loader.close()
        client.close()
    for rows, batch in zip(batches, got):
        assert np.array_equal(batch, reference_batch(files, rows))
    n_windows = sum(len(b) for b in batches)
    assert _count("Client.JaxWindowReads") - reads == n_windows
    assert _count("Client.JaxWindowMapped") - mapped == \
        n_windows - first_touches(batches)
    distinct = {int(i) for b in batches for i, _off in b}
    assert leased == len(distinct) == 6
    assert released == leased - 2


def test_a_revisit_through_a_stale_stream_leases_again(tmp_path):
    """Cache of 2 over 3 one-block files and a top tier of 3 blocks, of
    which a pass may keep 2 leased: read 0, 1, 2, 0, the stream the
    producer cached for file 0 is stale by the fourth window (its
    segment went), so that window takes a lease again and reads the
    right bytes; every lease but those of the segments held goes
    back."""
    with _cluster(tmp_path, tier=3) as cluster:
        paths, files = _write(cluster, 3, 1)
        client = _client(cluster, 2)
        loader = DeviceBlockLoader(client, paths)
        rows = [[0, 10], [1, 20], [2, 30], [0, 40]]
        leased, released = _served("shm_open"), _served("shm_release")
        mapped = _count("Client.JaxWindowMapped")
        try:
            got = [np.asarray(b) for b in loader.windows(
                [np.array([r]) for r in rows], window_bytes=WINDOW)]
            assert client.store.shm.drain(10.0)
            held = client.store.shm.cached_blocks()
            leased = _served("shm_open") - leased
            released = _served("shm_release") - released
        finally:
            loader.close()
            client.close()
    for r, batch in zip(rows, got):
        assert np.array_equal(batch[0], files[r[0]][r[1]:r[1] + WINDOW])
    assert held == 2
    assert leased == 4 and released == leased - held
    assert _count("Client.JaxWindowMapped") == mapped


def test_a_pass_leaves_the_tier_room_for_another_clients_write(tmp_path):
    """Every lease pins its block in the worker's tier. A one-tier worker
    of 4 blocks holds the loader's 4 files whole; the pass keeps at most
    3 of them leased (95% of the tier), so with all 4 read a second
    client's MUST_CACHE write still finds a block it may evict, and the
    pass's windows stay the reference's (the files are persisted, so
    the one evicted is read through again)."""
    with _cluster(tmp_path, tier=4, levels=1) as cluster:
        paths, files = _write(cluster, 4, 1, write_type="CACHE_THROUGH")
        client = _client(cluster, 2)
        loader = DeviceBlockLoader(client, paths)
        shm = client.store.shm
        rows = [[i % 4, 100 * i] for i in range(12)]
        writer = cluster.file_system()
        try:
            it = loader.windows([np.array([r]) for r in rows],
                                window_bytes=WINDOW)
            got = [np.asarray(next(it)) for _ in range(4)]
            assert shm.cached_blocks() <= 3
            writer.write_all("/windows/other", bytes(BLOCK),
                             write_type="MUST_CACHE")
            got += [np.asarray(b) for b in it]
        finally:
            loader.close()
            client.close()
    for r, batch in zip(rows, got):
        assert np.array_equal(batch[0], files[r[0]][r[1]:r[1] + WINDOW])


@pytest.mark.parametrize("bad", [
    ("past-eof", [0, BLOCK - WINDOW + 1], ValueError),
    ("negative-offset", [1, -1], ValueError),
    ("no-such-file", [3, 0], IndexError),
], ids=lambda b: b[0])
def test_a_window_out_of_its_file_fails_the_pass(cluster, bad):
    _name, row, err = bad
    paths, _files = _write(cluster, 3, 1)
    loader = DeviceBlockLoader(cluster.file_system(), paths)
    try:
        it = loader.windows([np.array([[0, 0], row])], window_bytes=WINDOW)
        with pytest.raises(err):
            next(it)
    finally:
        loader.close()


def _producers() -> list:
    return [t for t in threading.enumerate()
            if t.name.startswith("loader-host-prefetch")]


def test_closing_mid_pass_retires_the_producer(cluster):
    paths, files = _write(cluster, 4, 1)

    def endless():
        rng = np.random.default_rng(3)
        while True:
            yield np.stack([rng.integers(0, 4, size=5),
                            rng.integers(0, BLOCK - WINDOW, size=5)], axis=1)

    loader = DeviceBlockLoader(cluster.file_system(), paths)
    try:
        it = loader.windows(endless(), window_bytes=WINDOW)
        for _ in range(3):
            assert np.asarray(next(it)).shape == (5, WINDOW)
        assert len(_producers()) >= 1
        it.close()
        assert loader._producer_pool is None
        assert not [t for t in _producers() if t.is_alive()]
        # the loader is whole: an epoch after it reads every block
        assert len(list(loader.epoch())) == 4
    finally:
        loader.close()


def test_the_hbm_tier_keeps_no_batch(cluster):
    """A loader with an HBM tier reads windows past it: no batch is a
    page, and the epoch after it still misses every block once."""
    paths, files = _write(cluster, 3, 1)
    loader = DeviceBlockLoader(cluster.file_system(), paths,
                               hbm_bytes=8 * BLOCK)
    try:
        batches = uniform_batches(files, 3, 4)
        got = list(loader.windows(batches, window_bytes=WINDOW))
        assert loader.hbm_stats()["hbm_bytes"] == 0
        for rows, batch in zip(batches, got):
            assert np.array_equal(np.asarray(batch),
                                  reference_batch(files, rows))
        assert len(list(loader.epoch())) == 3
        assert loader.hbm_stats()["hbm_pages"] == 3
    finally:
        loader.close()


@pytest.fixture()
def ring():
    set_tracing_enabled(True)
    tracer().clear()
    yield tracer()
    set_tracing_enabled(False)
    tracer().clear()


def test_spans_a_batch_and_a_window(cluster, ring):
    paths, files = _write(cluster, 2, 2)
    batches = uniform_batches(files, 3, 6)
    batches.append(np.array([[1, BLOCK - 10]]))  # one split window
    loader = DeviceBlockLoader(cluster.file_system(), paths)
    # each batch read only once the one before is handed out: every
    # take of the consumer blocks on the queue
    step = Lockstep(loader)
    try:
        ring.clear()
        list(step.iterate(loader.windows(batches, window_bytes=WINDOW)))
        assert step.timed_out == 0
    finally:
        loader.close()
    spans = ring.snapshot(limit=4000)
    by_id = {s["span_id"]: s for s in spans}
    reads = [s for s in spans if s["name"] == "atpu.loader.host_read"]
    assert len(reads) == len(batches)
    slices = [len(b) + crosses(files, b) for b in batches]
    assert sorted((int(s["tags"]["windows"]), int(s["tags"]["blocks"]))
                  for s in reads) == sorted(
        (len(b), n) for b, n in zip(batches, slices))
    opens = [s for s in spans if s["name"] == "atpu.loader.open_block"]
    assert len(opens) == sum(slices)
    assert {by_id[s["parent"]]["name"] for s in opens} == {
        "atpu.loader.host_read"}
    # no prefault on this path, one device_put a batch
    assert not [s for s in spans if s["name"] == "atpu.loader.prefault"]
    assert len([s for s in spans if s["name"] == "atpu.loader.h2d"]) == \
        len(batches)
    # the consumer's side: one wait a batch, and one for the end
    assert len([s for s in spans if s["name"] == "atpu.loader.get_wait"]) \
        == len(batches) + 1


def test_the_first_batch_leaves_while_the_producer_holds_its_second(
        cluster):
    """One window a batch, inside one block: the producer's second
    read waits until the consumer has handed out the first batch."""
    paths, files = _write(cluster, 2, 1)
    batches = [np.array([[i % 2, 100 * i]]) for i in range(4)]
    loader = DeviceBlockLoader(cluster.file_system(), paths)
    gate, timed_out = held_after_first(loader, "_host_bytes")
    try:
        it = loader.windows(batches, window_bytes=WINDOW)
        got = [np.asarray(next(it))]
        assert timed_out == []
        gate.set()
        got += [np.asarray(b) for b in it]
    finally:
        gate.set()
        loader.close()
    assert len(got) == len(batches)
    for rows, batch in zip(batches, got):
        assert np.array_equal(batch, reference_batch(files, rows))


def test_the_suites_random_4k_row_reads_through_windows(cluster):
    """BASELINE #2's suite row runs the path users call: its reads are
    ``windows`` of 4,096 B, one batch a ``device_put``."""
    import jax

    from alluxio_tpu.stress.tpu_suite import config2_random_4k

    before = _counts()
    row = config2_random_4k(jax, cluster.file_system(), jax.devices()[0],
                            shard_bytes=3 * BLOCK, reads=200, batch=64)
    assert row["config"] == "2-random-4k" and row["unit"] == "MB/s"
    assert {"ops_per_s", "ceiling_mb_per_s", "achieved_vs_ceiling",
            "vs_baseline"} <= set(row)
    delta = {k: _count(k) - v for k, v in before.items()}
    assert delta["Client.JaxWindowReads"] == 200
    assert delta["Client.JaxWindowBatches"] == 4  # 64, 64, 64, 8
