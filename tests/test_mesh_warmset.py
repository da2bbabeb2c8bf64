"""The four-chip warm-set deployment (``mesh4-warmset-32m``) at small
size: ``MeshBlockCache`` on 4 of conftest's 8 CPU devices over a
``LocalCluster``, held byte for byte (uint8: no tolerance) to the plain
reference in ``testutils/mesh_reference.py`` on seeded contents. Also
its placement records at the master (one a warm set, two warm sets of
one host kept apart) and every span and counter of the load. Counts and
bytes only, never a speed."""

from __future__ import annotations

import numpy as np
import pytest

from alluxio_tpu.client.streams import WriteType
from alluxio_tpu.conf import Keys
from alluxio_tpu.metrics import metrics
from alluxio_tpu.minicluster import LocalCluster
from alluxio_tpu.parallel.ici_store import MeshBlockCache
from alluxio_tpu.parallel.mesh import make_mesh
from alluxio_tpu.utils.tracing import set_tracing_enabled, tracer
from tests.testutils import mesh_reference as ref

BLOCK = 8192
N_DEV = 4
SPANS = ("atpu.mesh.load_global", "atpu.mesh.read_shard", "atpu.mesh.stack",
         "atpu.mesh.device_put", "atpu.mesh.report_placement")
COUNTERS = ("Client.JaxMeshBlocksLoaded", "Client.JaxMeshBytesLoaded",
            "Client.JaxMeshHostReadUs", "Client.JaxMeshStackUs",
            "Client.JaxMeshPutUs", "Client.JaxMeshPlacementReports",
            "Client.JaxMeshPlacementReportFailures")


@pytest.fixture(scope="module")
def mesh():
    import jax

    return make_mesh(devices=jax.devices()[:N_DEV])


@pytest.fixture()
def cluster(tmp_path):
    # a segment cache of 2: every load below takes more leases than the
    # transport keeps mapped, as the full-size load does (512 through 64)
    with LocalCluster(str(tmp_path), num_workers=1, block_size=BLOCK,
                      worker_mem_bytes=64 << 20, conf_overrides={
                          Keys.USER_SHM_SEGMENT_CACHE_MAX: 2}) as c:
        yield c


@pytest.fixture()
def ring():
    set_tracing_enabled(True)
    tracer().clear()
    yield tracer()
    set_tracing_enabled(False)
    tracer().clear()


def _write(fs, seed: int, n: int, last_bytes: int = BLOCK, prefix="/warm"):
    """``n`` one-block files of seeded bytes (the last one may be
    short); returns their paths and the bytes acknowledged."""
    rng = np.random.default_rng([seed, n])
    paths, blocks = [], []
    for i in range(n):
        size = last_bytes if i == n - 1 else BLOCK
        data = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
        paths.append(f"{prefix}-{seed}/b{i:03d}")
        fs.write_all(paths[-1], data, write_type=WriteType.MUST_CACHE)
        blocks.append(data)
    return paths, blocks


def _counts() -> dict:
    snap = metrics().snapshot()
    return {name: snap.get(name, 0) for name in COUNTERS}


def _record(bc, cache) -> dict:
    """``{global index: [(host, mesh position), ...]}`` as the master
    answers ``get_block_info`` for every block of ``cache``."""
    out = {}
    for g, bid in enumerate(cache.block_ids):
        out[g] = [(loc.address.host,
                   int(loc.address.tiered_identity.value("mesh")))
                  for loc in bc.get_block_info(bid).device_locations]
    return out


@pytest.mark.parametrize("n,last_bytes", [
    (8, BLOCK),          # divisible by 4
    (6, BLOCK),          # ragged tail: two zero rows on the last owner
    (7, BLOCK // 2 + 3),  # ragged tail AND a short last block
    (13, BLOCK),         # more blocks a shard than the segment cache
])
def test_load_global_equals_the_reference_table_row_for_row(
        cluster, mesh, n, last_bytes):
    fs = cluster.file_system()
    assert fs.store.shm._cache_max == 2
    paths, blocks = _write(fs, 33, n, last_bytes)
    cache = MeshBlockCache(mesh, block_bytes=BLOCK)
    cached = cache.load_global(fs, paths)
    want = ref.table(blocks, N_DEV, BLOCK)
    assert cached.shape == want.shape
    assert np.array_equal(np.asarray(cached), want)
    # the real sharding is the reference's ownership
    per_dev = ref.per_dev(n, N_DEV)
    for pos, rows in cache.describe_placement(cached).items():
        assert rows == list(range(pos * per_dev, (pos + 1) * per_dev))
        assert all(ref.owner(g, n, N_DEV) == pos for g in rows)
    cache.drop_placement(fs)
    fs.close()


#: case -> (blocks written, the cache's dtype). 11 blocks: per_dev 3,
#: the last owner holds two blocks and a zero row
BATCH_CASES = {
    "every_owner": (11, np.uint8),
    "duplicates": (11, np.uint8),
    "last_padded_index": (11, np.uint8),
    "one_row": (11, np.uint8),
    "batch_not_a_multiple_of_the_devices": (11, np.uint8),
    "more_rows_than_one_tile": (11, np.uint8),
    "one_block_a_device": (4, np.uint8),
    "index_past_the_end": (11, np.uint8),
    "negative_index": (11, np.uint8),
    "uint16_cache": (11, np.uint16),
    "int32_cache": (6, np.int32),
}


def _batches(case: str, n: int, per_dev: int, rng):
    if case == "every_owner":
        # two rows of every owner's shard, shuffled
        batches = [rng.permutation(np.concatenate([
            pos * per_dev + rng.choice(min(per_dev, n - pos * per_dev), 2,
                                       replace=False)
            for pos in range(N_DEV)])) for _ in range(4)]
        assert all(sorted(ref.owner(g, n, N_DEV) for g in b)
                   == [0, 0, 1, 1, 2, 2, 3, 3] for b in batches)
        return batches
    if case == "duplicates":
        return [np.array([4, 4, 9, 0, 4, 9, 0, 0]), np.full(8, 7)]
    if case == "last_padded_index":
        return [np.array([11, 0, 10, 9]), np.array([11])]
    if case == "one_row":
        return [np.array([g]) for g in (0, 5, 10)]
    if case == "batch_not_a_multiple_of_the_devices":
        return [np.array([9, 2, 6]), np.array([1, 1, 10, 3, 8]),
                np.array([7, 0, 5, 4, 2, 9, 9])]
    if case == "more_rows_than_one_tile":
        # 9, 16 and 19 rows: past one group of eight, two whole groups,
        # two groups and a tail
        return [rng.integers(0, N_DEV * per_dev, size=b) for b in (9, 16, 19)]
    if case == "one_block_a_device":
        return [np.array([3, 0, 2, 1]), np.array([2, 2, 0]), np.array([1])]
    if case == "index_past_the_end":
        # 12 rows in the table: 12 is the first index no owner holds
        return [np.array([12, 3, 100, 11]), np.array([2 ** 31 - 1])]
    if case == "negative_index":
        return [np.array([-1, 3, -12, 0]), np.array([-13]),
                np.array([-(2 ** 31), 10])]
    # a cache of wider elements: any rows, every owner among them
    return [rng.permutation(n)[:5], np.array([n - 1, 0, n - 1])]


@pytest.mark.parametrize("case", list(BATCH_CASES))
def test_global_batch_and_batch_fn_equal_the_reference(cluster, mesh, case):
    """Byte for byte against the plain reference; an index no owner
    holds (past the last padded row, or negative) is a zero row."""
    import jax.numpy as jnp

    n, dtype = BATCH_CASES[case]
    fs = cluster.file_system()
    paths, blocks = _write(fs, 34, n)
    cache = MeshBlockCache(mesh, block_bytes=BLOCK, dtype=dtype)
    cached = cache.load_global(fs, paths, report=False)
    assert cached.dtype == dtype
    assert cached.shape == (N_DEV * ref.per_dev(n, N_DEV),
                            BLOCK // np.dtype(dtype).itemsize)
    want = ref.table(blocks, N_DEV, BLOCK)
    per_dev = ref.per_dev(n, N_DEV)
    rng = np.random.default_rng([34, len(case)])
    fn = cache.batch_fn(per_dev)
    for idx in _batches(case, n, per_dev, rng):
        got = cache.global_batch(cached, idx)
        assert got.dtype == dtype and got.sharding.is_fully_replicated
        got = np.asarray(got)
        assert np.array_equal(got.view(np.uint8), ref.batch(want, idx))
        fused = np.asarray(fn(cached, jnp.asarray(idx, jnp.int32)))
        assert np.array_equal(fused, got)
    fs.close()


def test_batch_assembly_lowers_without_all_gather(cluster, mesh):
    import jax.numpy as jnp

    fs = cluster.file_system()
    paths, _blocks = _write(fs, 35, 8)
    cache = MeshBlockCache(mesh, block_bytes=BLOCK)
    cached = cache.load_global(fs, paths, report=False)
    hlo = cache.batch_fn(2).lower(
        cached, jnp.arange(8, dtype=jnp.int32)).compile().as_text()
    assert "all-gather" not in hlo and "all-reduce" in hlo
    fs.close()


@pytest.mark.parametrize("batch", [8, 19])
def test_batch_assembly_lowers_to_row_copies_and_one_all_reduce(
        cluster, mesh, batch):
    """A chip builds its contribution by row copies out of its shard,
    not by a gather, and the exchange stays ONE all-reduce of the batch
    (here the Pallas interpreter's lowering; the chip's own is compiled
    in ``test_record_batches.py``, beside the other v5e compiles)."""
    import jax.numpy as jnp

    fs = cluster.file_system()
    paths, _blocks = _write(fs, 42, 8)
    cache = MeshBlockCache(mesh, block_bytes=BLOCK)
    cached = cache.load_global(fs, paths, report=False)
    hlo = cache.batch_fn(2).lower(
        cached, jnp.arange(batch, dtype=jnp.int32) % 8).compile().as_text()
    assert " all-reduce(" in hlo and " all-gather(" not in hlo
    assert hlo.count(" all-reduce(") == 1
    assert " gather(" not in hlo  # "all-gather(" does not hide it
    fs.close()


def test_the_masters_record_names_the_owning_position_of_every_block(
        cluster, mesh):
    fs = cluster.file_system()
    n = 7
    paths, _blocks = _write(fs, 36, n)
    cache = MeshBlockCache(mesh, block_bytes=BLOCK)
    cache.load_global(fs, paths)
    bc = cluster.block_client()
    assert _record(bc, cache) == {
        g: [(cache.client_host, ref.owner(g, n, N_DEV))] for g in range(n)}
    cache.drop_placement(fs)
    assert bc.device_block_map() == {}
    fs.close()


def test_two_warm_sets_of_one_process_keep_separate_records(cluster, mesh):
    """A second cache loaded beside the first (a cold job start beside a
    live warm set; same host, same blocks, same positions) leaves the
    first one's record intact, and dropping either leaves the other's."""
    fs = cluster.file_system()
    n = 8
    paths, _blocks = _write(fs, 37, n)
    bc = cluster.block_client()
    first = MeshBlockCache(mesh, block_bytes=BLOCK)
    first.load_global(fs, paths)
    want = {g: [(first.client_host, ref.owner(g, n, N_DEV))]
            for g in range(n)}
    assert _record(bc, first) == want
    second = MeshBlockCache(mesh, block_bytes=BLOCK)
    assert second.client_host == first.client_host
    assert second.reporter != first.reporter
    second.load_global(fs, paths)
    # one host at one position is ONE location, whoever reported it
    assert _record(bc, first) == want
    second.drop_placement(fs)
    assert _record(bc, first) == want, \
        "dropping the second warm set took the first one's record"
    # and the other way round: a third set stays when the first goes
    third = MeshBlockCache(mesh, block_bytes=BLOCK)
    third.load_global(fs, paths[:4])  # per_dev 1: block g at position g
    first.drop_placement(fs)
    assert _record(bc, third) == {
        g: [(third.client_host, g)] for g in range(4)}
    assert sorted(bc.device_block_map()) == sorted(third.block_ids)
    third.drop_placement(fs)
    assert bc.device_block_map() == {}
    fs.close()


def test_reports_of_two_reporters_age_out_by_reporter(cluster):
    """The master's side alone: a host's two reporters are two leases;
    a caller that names no reporter is its host's one report."""
    bm = cluster.master.block_master
    bm.report_device_blocks("h", {0: [1, 2]}, reporter="h/a")
    bm.report_device_blocks("h", {0: [2], 1: [3]}, reporter="h/b")
    bm.report_device_blocks("old", {5: [9]})
    assert bm.device_block_map() == {
        1: {0: "h"}, 2: {0: "h"}, 3: {1: "h"}, 9: {5: "old"}}
    bm.report_device_blocks("h", {0: [2]}, reporter="h/a")  # a turnover
    assert bm.device_block_map() == {2: {0: "h"}, 3: {1: "h"}, 9: {5: "old"}}
    bm.clear_device_blocks("h", "h/b")
    assert bm.device_block_map() == {2: {0: "h"}, 9: {5: "old"}}
    bm.report_device_blocks("old", {})  # no reporter: the host's own
    assert bm.device_block_map() == {2: {0: "h"}}
    bm.report_device_blocks("h", {1: [4]}, reporter="h/b")
    bm.device_report_ttl_ms = -1
    assert bm.prune_device_reports() == ["h"]  # two leases, one host
    assert bm.device_block_map() == {}


def test_load_global_records_every_span_and_counter(cluster, mesh, ring):
    fs = cluster.file_system()
    n = 7
    paths, _blocks = _write(fs, 38, n, BLOCK - 5)
    cache = MeshBlockCache(mesh, block_bytes=BLOCK)
    before = _counts()
    cache.load_global(fs, paths)
    got = {k: v - before[k] for k, v in _counts().items()}
    assert got["Client.JaxMeshBlocksLoaded"] == n
    assert got["Client.JaxMeshBytesLoaded"] == n * BLOCK
    assert got["Client.JaxMeshPlacementReports"] == 1
    assert got["Client.JaxMeshPlacementReportFailures"] == 0
    for name in ("Client.JaxMeshHostReadUs", "Client.JaxMeshStackUs",
                 "Client.JaxMeshPutUs"):
        assert got[name] > 0
    spans = {}
    for s in ring.snapshot(limit=4000, prefix="atpu.mesh."):
        spans.setdefault(s["name"], []).append(s)
    assert sorted(spans) == sorted(SPANS)
    (whole,) = spans["atpu.mesh.load_global"]
    assert whole["tags"] == {"blocks": "7", "devices": "4",
                             "bytes": str(8 * BLOCK)}
    shards = sorted(spans["atpu.mesh.read_shard"],
                    key=lambda s: int(s["tags"]["pos"]))
    assert [s["tags"] for s in shards] == [
        {"pos": str(p), "blocks": str(b)}
        for p, b in enumerate([2, 2, 2, 1])]
    assert len(spans["atpu.mesh.stack"]) == N_DEV
    assert [s["tags"] for s in spans["atpu.mesh.device_put"]] \
        == [{"bytes": str(2 * BLOCK)}] * N_DEV
    (report,) = spans["atpu.mesh.report_placement"]
    # everything of the load is inside the load's span, in its trace
    for name in SPANS[1:]:
        for s in spans[name]:
            assert s["trace_id"] == whole["trace_id"]
            assert s["start_ms"] >= whole["start_ms"]
    assert report["parent"] == whole["span_id"]
    assert all(s["parent"] == whole["span_id"] for s in shards)
    cache.drop_placement(fs)
    fs.close()


def test_turnover_takes_the_loads_span_names_and_counters(
        cluster, mesh, ring):
    fs = cluster.file_system()
    paths, blocks = _write(fs, 39, 8)
    fresh_paths, fresh = _write(fs, 40, 2, prefix="/fresh")
    cache = MeshBlockCache(mesh, block_bytes=BLOCK)
    cached = cache.load_global(fs, paths)
    ring.clear()
    before = _counts()
    cached = cache.turnover(cached, fs, {1: (fresh_paths[0], 0),
                                         6: (fresh_paths[1], 0)})
    got = {k: v - before[k] for k, v in _counts().items()}
    assert got["Client.JaxMeshBlocksLoaded"] == 2
    assert got["Client.JaxMeshBytesLoaded"] == 2 * BLOCK
    assert got["Client.JaxMeshPlacementReports"] == 1
    names = sorted(s["name"] for s in
                   ring.snapshot(limit=4000, prefix="atpu.mesh."))
    assert names == sorted(["atpu.mesh.read_shard", "atpu.mesh.stack",
                            "atpu.mesh.device_put"] * 2
                           + ["atpu.mesh.report_placement"])
    blocks[1], blocks[6] = fresh[0], fresh[1]
    assert np.array_equal(np.asarray(cached),
                          ref.table(blocks, N_DEV, BLOCK))
    cache.drop_placement(fs)
    fs.close()


@pytest.mark.parametrize("call", ["report_placement", "drop_placement"])
def test_a_failed_placement_call_is_counted_and_swallowed(
        cluster, mesh, call):
    class Refusing:
        def report_device_blocks(self, *a, **kw):
            raise ConnectionError("master away")

        clear_device_blocks = report_device_blocks

    fs = cluster.file_system()
    paths, blocks = _write(fs, 41, 4)
    cache = MeshBlockCache(mesh, block_bytes=BLOCK)
    cache._block_client = Refusing()
    before = _counts()
    if call == "report_placement":
        cached = cache.load_global(fs, paths)  # reports, and goes on
        assert np.array_equal(np.asarray(cached),
                              ref.table(blocks, N_DEV, BLOCK))
    else:
        cache.drop_placement(fs)
    got = {k: v - before[k] for k, v in _counts().items()}
    assert got["Client.JaxMeshPlacementReportFailures"] == 1
    assert got["Client.JaxMeshPlacementReports"] == 0
    fs.close()
