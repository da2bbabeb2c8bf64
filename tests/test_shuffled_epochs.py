"""The shuffled-epoch deployment, tiny, against its plain reference: a
set larger than the HBM tier read in the seeded per-epoch permutation
through the clairvoyant prefetch service (heartbeat thread running,
``hbm.fraction`` 1.0), on a real minicluster and the CPU.

The reference is a few lines of NumPy and knows nothing of
``alluxio_tpu.prefetch``: the order of epoch ``e`` is the permutation
drawn from ``SeedSequence([seed, e])`` (the oracle's documented
contract, on which two hosts agreeing depends), the bytes are the
seed's generator (the benchmark's ``ByteSet``, copied)."""

import threading
from collections import OrderedDict

import numpy as np
import pytest

from alluxio_tpu.client.cache.evictor import (
    LRUCacheEvictor, NextUseCacheEvictor,
)
from alluxio_tpu.client.file_system import FileSystem
from alluxio_tpu.client.jax_io import DeviceBlockLoader
from alluxio_tpu.conf import Keys
from alluxio_tpu.metrics import metrics
from alluxio_tpu.minicluster import LocalCluster
from alluxio_tpu.prefetch import DatasetManifest, PrefetchService
from alluxio_tpu.utils.tracing import set_tracing_enabled, tracer

BLOCK = 1 << 20
N_FILES = 12
TIER_BLOCKS = 8
EPOCHS = 4
SEED = 2**31 + 35
#: items between the producer's look-up in the HBM tier and the adopt on
#: the consumer's side of the queue: the queue's ``prefetch + 1`` and
#: the one in the producer's hand
QUEUE_DEPTH = 4


# -- the plain reference ----------------------------------------------------
def reference_order(seed: int, epoch: int, n: int) -> np.ndarray:
    return np.random.default_rng(
        np.random.SeedSequence([seed, epoch])).permutation(n)


class ReferenceBytes:
    """File ``i`` = a seeded base block + i (mod 256), its index stamped
    in the first 8 bytes."""

    def __init__(self, seed: int, file_bytes: int) -> None:
        self._base = np.random.default_rng([seed, 0]).integers(
            0, 256, size=file_bytes, dtype=np.uint8)

    def file(self, i: int) -> np.ndarray:
        out = self._base + np.uint8(i % 256)
        out[:8] = np.frombuffer(np.uint64(i).tobytes(), dtype=np.uint8)
        return out


class Lru:
    """A plain LRU of ``capacity`` entries; ``hits(order)`` runs the
    accesses through it and counts those it held."""

    def __init__(self, capacity: int) -> None:
        self.capacity = capacity
        self.held: OrderedDict = OrderedDict()

    def hits(self, order) -> int:
        n = 0
        for x in order:
            if x in self.held:
                self.held.move_to_end(x)
                n += 1
            else:
                self.held[x] = True
                if len(self.held) > self.capacity:
                    self.held.popitem(last=False)
        return n


# -- the job as a user writes it --------------------------------------------
@pytest.fixture()
def cluster(tmp_path):
    with LocalCluster(str(tmp_path), num_workers=1, block_size=BLOCK,
                      worker_mem_bytes=64 << 20) as c:
        yield c


@pytest.fixture()
def dataset(cluster):
    fs = cluster.file_system()
    ref = ReferenceBytes(SEED, BLOCK)
    paths = [f"/shuffled/shard-{i:04d}" for i in range(N_FILES)]
    for i, path in enumerate(paths):
        fs.write_all(path, ref.file(i).tobytes(), write_type="MUST_CACHE")
    return fs, paths, ref


def _job(cluster, fs, paths, *, budget_blocks: int, heartbeat="100ms"):
    conf = cluster.conf.copy()
    conf.set(Keys.PREFETCH_ENABLED, True)
    conf.set(Keys.PREFETCH_HBM_FRACTION, 1.0)
    conf.set(Keys.PREFETCH_BUDGET_BYTES, budget_blocks * BLOCK)
    conf.set(Keys.PREFETCH_LOOKAHEAD_BLOCKS, 8)
    conf.set(Keys.PREFETCH_HEARTBEAT_INTERVAL, heartbeat)
    svc = PrefetchService.from_conf(conf, fs, paths, seed=SEED)
    loader = DeviceBlockLoader(fs, paths, hbm_bytes=TIER_BLOCKS * BLOCK,
                               prefetch_service=svc)
    svc.start()
    return svc, loader


def _count(name: str) -> float:
    return metrics().snapshot().get(name, 0)


def _served(method: str, role: str = "Master") -> float:
    return sum(_count(f"{role}.RpcServed.{route}.{method}")
               for route in ("fastpath", "grpc"))


def _read_every_epoch(svc, loader, ref):
    """Every block of every epoch byte for byte in the reference order,
    every step classified once, the store within capacity, nothing
    offered to the tier turned away."""
    base = svc.stats()
    rejected = _count("Client.JaxHbmAdoptRejected")
    cap = TIER_BLOCKS * BLOCK
    for epoch in range(EPOCHS):
        want = reference_order(SEED, epoch, N_FILES)
        assert sorted(want) == list(range(N_FILES))  # each once
        got = 0
        for i, block in zip(want, loader.epoch()):
            assert np.array_equal(np.asarray(block), ref.file(i)), \
                f"epoch {epoch} position {got}: not file {i}"
            assert loader.hbm_stats()["hbm_bytes"] <= cap
            got += 1
        assert got == N_FILES
    stats = svc.stats()
    consumed = sum(stats[k] - base[k] for k in ("hits", "late", "misses"))
    assert consumed == EPOCHS * N_FILES
    assert loader.hbm_stats()["hbm_bytes"] <= cap
    assert _count("Client.JaxHbmAdoptRejected") == rejected


# budget 0 is the order alone; with a budget the agent's adopt thread
# and the producer share the lease plane and the tier (a 2 ms heartbeat,
# so that the agent ticks many times within an epoch of 12 small blocks)
@pytest.mark.parametrize("budget_blocks,heartbeat", [
    (0, "100ms"), (2, "2ms"), (4, "2ms")])
def test_every_epoch_is_the_reference_permutation_byte_for_byte(
        cluster, dataset, budget_blocks, heartbeat):
    fs, paths, ref = dataset
    svc, loader = _job(cluster, fs, paths, budget_blocks=budget_blocks,
                       heartbeat=heartbeat)
    try:
        _read_every_epoch(svc, loader, ref)
    finally:
        loader.close()
        svc.close()


@pytest.mark.parametrize("budget_blocks", [2, 4])
def test_two_threads_turn_one_segment_cache_over_on_every_open(
        cluster, dataset, budget_blocks):
    """The same job through a segment cache of 2 (the cell reads 384
    blocks through 64): the producer and the agent's adopt thread turn
    ONE cache over on every open, so ``ShmTransport._map`` makes room
    on each thread beside the other's opens and views, and the victims'
    unmaps and leases given back run on the transport's own thread
    beside both."""
    _fs, paths, ref = dataset
    conf = cluster.conf.copy()
    conf.set(Keys.USER_SHM_SEGMENT_CACHE_MAX, 2)
    client = FileSystem(cluster.master.address, conf=conf)
    svc, loader = _job(cluster, client, paths,
                       budget_blocks=budget_blocks, heartbeat="2ms")
    opened = _count("Client.JaxShortCircuitBlocks")
    leased = _served("shm_open", "Worker")
    released = _served("shm_release", "Worker")
    try:
        _read_every_epoch(svc, loader, ref)
        svc.close()  # the adopt thread is done before the counts are read
        # and so is the transport's own, which gives the leases back
        assert client.store.shm.drain(10.0)
        opened = _count("Client.JaxShortCircuitBlocks") - opened
        leased = _served("shm_open", "Worker") - leased
        released = _served("shm_release", "Worker") - released
        # epoch 0 misses every block, a later one what the tier cannot hold
        assert opened >= N_FILES + (EPOCHS - 1) * (N_FILES - TIER_BLOCKS)
        held = client.store.shm.cached_blocks()
        assert held <= 2
        # no lease is orphaned and the cache DID turn over: every lease
        # granted went back to the worker but those of the segments
        # still held. (An open that took no lease found the segment the
        # OTHER thread had just mapped, both having missed the block:
        # ``opened`` counts views, and runs ahead of ``leased``.)
        assert leased - released == held, (opened, leased, released)
        assert leased >= N_FILES  # epoch 0 alone leases every block
        assert released >= opened // 2, (opened, leased, released)
    finally:
        loader.close()
        svc.close()
        client.close()


def next_use_hits(orders, capacity: int, epochs: int):
    """Hits an epoch of a plain next-use tier of ``capacity`` entries on
    ``orders`` (one array an epoch, one more than ``epochs`` so that the
    last epoch's pages have a next use): a miss is taken in, and where
    the tier is full the held entry used farthest ahead goes first."""
    seq = [int(x) for order in orders for x in order]
    n = len(orders[0])

    def next_use(x, t):
        return next((u for u in range(t + 1, len(seq)) if seq[u] == x),
                    len(seq))

    held, hits = set(), [0] * epochs
    for t in range(epochs * n):
        if seq[t] in held:
            hits[t // n] += 1
            continue
        if len(held) == capacity:
            held.remove(max(held, key=lambda y: next_use(y, t)))
        held.add(seq[t])
    return hits


@pytest.mark.parametrize("with_service", [True, False],
                         ids=["oracle-order", "file-order"])
def test_the_tier_evicts_by_next_use_only_where_the_order_is_known(
        cluster, dataset, with_service):
    """Budget 0: the service gives the order and places nothing. A
    loader bound to it knows what is read next, and its HBM tier hits
    what a plain next-use tier hits on the reference order (to within
    the items that lie between the producer's look-up and the
    consumer's adopt), which is more than LRU can. A loader with no
    service reads in file order and keeps LRU: a cyclic scan of 12
    through a tier of 8 hits nothing but what the queue's depth lets
    through, where next use would hit 7 an epoch."""
    fs, paths, _ref = dataset
    if with_service:
        svc, loader = _job(cluster, fs, paths, budget_blocks=0)
        orders = [reference_order(SEED, e, N_FILES)
                  for e in range(EPOCHS + 1)]
    else:
        svc = None
        loader = DeviceBlockLoader(fs, paths,
                                   hbm_bytes=TIER_BLOCKS * BLOCK)
        orders = [np.arange(N_FILES)] * (EPOCHS + 1)
    lru = Lru(TIER_BLOCKS)
    lru_hits = [lru.hits(order) for order in orders[:EPOCHS]]
    ahead_hits = next_use_hits(orders, TIER_BLOCKS, EPOCHS)
    assert sum(ahead_hits) > sum(lru_hits)  # the order is worth knowing
    want = ahead_hits if with_service else lru_hits
    total = 0
    adopted = _count("Client.PrefetchHbmAdopted")
    rejected = _count("Client.JaxHbmAdoptRejected")
    cap = TIER_BLOCKS * BLOCK
    try:
        for epoch in range(EPOCHS):
            before = _count("Client.JaxHbmHits")
            got = 0
            for _block in loader.epoch():
                assert loader.hbm_stats()["hbm_bytes"] <= cap
                got += 1
            assert got == N_FILES
            hits = _count("Client.JaxHbmHits") - before
            assert abs(hits - want[epoch]) <= QUEUE_DEPTH, \
                (epoch, hits, want)
            total += hits
        if with_service:
            assert sum(lru_hits) < total < EPOCHS * N_FILES
            assert svc.stats()["hits"] == total  # the consumer's view
        # ONE place chooses, from whether a service is bound
        assert type(loader._hbm._evictor) is (
            NextUseCacheEvictor if with_service else LRUCacheEvictor)
        assert _count("Client.PrefetchHbmAdopted") == adopted
        assert _count("Client.JaxHbmAdoptRejected") == rejected
    finally:
        loader.close()
        if svc is not None:
            svc.close()


def test_a_closed_loader_goes_with_its_last_reference(cluster, dataset):
    """The tier's evictor asks the service, not the loader: a job that
    closes its loader frees it (plan, statuses, tier) at once, with no
    pass of the cycle collector, which would land in the next job's
    start."""
    import gc
    import weakref

    fs, paths, _ref = dataset
    svc, loader = _job(cluster, fs, paths, budget_blocks=0)
    assert len(list(loader.epoch())) == N_FILES
    gone = weakref.ref(loader)
    gc.collect()
    gc.disable()
    try:
        loader.close()
        svc.close()
        del loader
        assert gone() is None
    finally:
        gc.enable()


def test_a_shuffled_job_start_makes_no_call_a_file(cluster, dataset):
    fs, paths, _ref = dataset
    fs.write_all("/shuffled/ragged", b"r" * (2 * BLOCK + 5),
                 write_type="MUST_CACHE")
    paths = paths + ["/shuffled/ragged"]
    client = cluster.file_system()  # a job's own client: nothing cached
    set_tracing_enabled(True)
    tracer().clear()
    try:
        calls = {m: _served(m) for m in (
            "get_file_block_info_list", "get_status", "get_status_many")}
        svc = PrefetchService.from_conf(_enabled(cluster), client, paths,
                                        seed=SEED)
        loader = DeviceBlockLoader(client, paths, hbm_bytes=BLOCK,
                                   prefetch_service=svc)
        made = {m: _served(m) - n for m, n in calls.items()}
        spans = [s for s in tracer().snapshot()
                 if s["name"] == "atpu.prefetch.manifest"]
    finally:
        set_tracing_enabled(False)
        tracer().clear()
    try:
        assert made == {"get_file_block_info_list": 0, "get_status": 0,
                        "get_status_many": 1}
        assert len(spans) == 1
        assert {k: int(v) for k, v in spans[0]["tags"].items()} == {
            "files": N_FILES + 1, "blocks": N_FILES + 3, "calls": 1}
        # what the one call gave is what a call a file would have given
        blocks = iter(svc.oracle.manifest.blocks)
        for path in paths:
            for i, fbi in enumerate(
                    client.fs_master.get_file_block_info_list(path)):
                b = next(blocks)
                assert (b.path, b.block_index, b.block_id, b.length,
                        b.offset) == (path, i, fbi.block_info.block_id,
                                      fbi.block_info.length, fbi.offset)
        assert next(blocks, None) is None
        assert isinstance(svc.oracle.manifest, DatasetManifest)
    finally:
        loader.close()
        svc.close()
        client.close()


def _enabled(cluster):
    conf = cluster.conf.copy()
    conf.set(Keys.PREFETCH_ENABLED, True)
    return conf


def test_a_block_both_threads_missed_is_one_duplicate_and_one_lease(
        cluster, dataset, monkeypatch):
    """The agent's adopt thread and the producer miss the same block
    side by side: both lease it, both transfer it. The transport keeps
    ONE segment (the second lease goes back at once, the first is not
    orphaned), the store keeps ONE page and counts the other transfer."""
    from alluxio_tpu.rpc.clients import WorkerClient

    fs, paths, ref = dataset
    client = cluster.file_system()
    svc = PrefetchService.from_conf(_enabled(cluster), client, paths,
                                    seed=SEED)
    loader = DeviceBlockLoader(client, paths, hbm_bytes=4 * BLOCK,
                               prefetch_service=svc)
    shm_store = cluster.workers[0].worker.shm_store
    both_leased = threading.Barrier(2, timeout=20)
    real_open = WorkerClient.shm_open

    def shm_open(self, session_id, block_id):
        lease = real_open(self, session_id, block_id)
        both_leased.wait()  # neither has mapped yet, both hold a lease
        return lease

    monkeypatch.setattr(WorkerClient, "shm_open", shm_open)
    target = 5
    block_ref = svc.oracle.manifest.blocks[target]
    before = {n: _count(n) for n in (
        "Client.JaxHbmAdopts", "Client.JaxHbmAdoptDuplicates")}
    leases = shm_store.stats()["live_leases"]
    out = {}

    def run(name, fn, *args):
        try:
            out[name] = fn(*args)
        except BaseException as e:  # noqa: BLE001 - asserted on below
            out[name] = e

    threads = [
        threading.Thread(target=run, args=(
            "producer", loader.load_block, target)),
        threading.Thread(target=run, args=(
            "agent", loader.prefetch_into_hbm, block_ref))]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        assert out["agent"] is True
        assert np.array_equal(np.asarray(out["producer"]),
                              ref.file(target))
        assert _count("Client.JaxHbmAdopts") \
            - before["Client.JaxHbmAdopts"] == 1
        assert _count("Client.JaxHbmAdoptDuplicates") \
            - before["Client.JaxHbmAdoptDuplicates"] == 1
        assert shm_store.stats()["live_leases"] - leases == 1
        assert client.store.shm.cached_blocks() == 1
        assert loader.hbm_stats() == {"hbm_bytes": BLOCK, "hbm_pages": 1}
    finally:
        loader.close()
        svc.close()
        client.close()
