"""A Parquet projection landed on the device through the loader a user
already builds: ``DeviceBlockLoader.row_groups`` against a plain NumPy
reference, on a real minicluster and the CPU.

The reference knows nothing of ``alluxio_tpu`` or of Parquet decoding:
every column is drawn from the seed as integers (a decimal as its
unscaled value) with a NULL mask, and a row group is the rows
``[k * group, (k + 1) * group)`` of its file, padded with zeros and
False to the pass's row count."""

import threading

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from alluxio_tpu.client.jax_io import DeviceBlockLoader
from alluxio_tpu.conf import Keys
from alluxio_tpu.metrics import metrics
from alluxio_tpu.minicluster import LocalCluster
from alluxio_tpu.table import plan as tplan
from alluxio_tpu.table.reader import read_columns
from alluxio_tpu.utils.tracing import set_tracing_enabled, tracer
from tests.testutils.loader_gates import held_after_first

#: small enough that a column chunk of a file crosses a block
BLOCK = 64 << 10
SEED = 2**31 + 42
#: name -> (arrow type, nullable, low, high) of the reference's columns
SCHEMA = {
    "key": (pa.int32(), False, 1, 204_000),
    "date_sk": (pa.int32(), True, 2_450_816, 2_452_642),
    "ticket": (pa.int64(), True, -(1 << 40), 1 << 40),
    "price": (pa.decimal128(7, 2), True, -9_999_999, 9_999_999),
    "amount": (pa.decimal128(12, 2), True, -(10**12 - 1), 10**12 - 1),
    "ratio": (pa.float64(), True, -1_000, 1_000),
}
COUNTERS = ("Client.JaxTableRowGroups", "Client.JaxTableRows",
            "Client.JaxTableFetchedBytes", "Client.JaxTableRowGroupBytes",
            "Client.JaxTablePlanMisses", "Client.TableFooterReads",
            "Client.StatusBatchCalls")


def _counts() -> dict:
    snap = metrics().snapshot()
    return {name: snap.get(name, 0) for name in COUNTERS}


def _delta(before: dict) -> dict:
    return {k: v - before[k] for k, v in _counts().items()}


# -- the plain reference ----------------------------------------------------
def reference_file(i: int, rows: int) -> dict:
    """``{name: (values, valid)}`` of file ``i``: one seeded stream a
    (file, column), a NULL in every nullable column at ~10%."""
    out = {}
    for c, (name, (typ, nullable, lo, hi)) in enumerate(SCHEMA.items()):
        rng = np.random.default_rng([SEED, i, c])
        if pa.types.is_floating(typ):
            vals = rng.integers(lo, hi, size=rows) / 8.0
        else:
            vals = rng.integers(lo, hi, size=rows, endpoint=True)
        valid = rng.random(rows) >= 0.1 if nullable else np.ones(rows, bool)
        if nullable:
            valid[1] = False  # a NULL in every nullable column
        out[name] = (vals, valid)
    return out


def _arrow(typ, vals, valid):
    """The column as pyarrow holds it, built from buffers: a decimal's
    128-bit words from its unscaled int64 (no Python ``Decimal``)."""
    n = len(vals)
    bitmap = pa.py_buffer(np.packbits(valid, bitorder="little"))
    if pa.types.is_decimal(typ):
        lo = vals.astype(np.int64)
        words = np.stack([lo, np.where(lo < 0, -1, 0)], axis=1)
        data = pa.py_buffer(np.ascontiguousarray(words).tobytes())
    else:
        data = pa.py_buffer(vals.astype(typ.to_pandas_dtype()).tobytes())
    return pa.Array.from_buffers(typ, n, [bitmap, data],
                                 null_count=int(n - valid.sum()))


def parquet_file(i: int, rows: int, group: int) -> bytes:
    cols = reference_file(i, rows)
    schema = pa.schema([pa.field(name, typ, nullable=nullable)
                        for name, (typ, nullable, _lo, _hi) in
                        SCHEMA.items()])
    table = pa.table({name: _arrow(SCHEMA[name][0], *cols[name])
                      for name in SCHEMA}, schema=schema)
    sink = pa.BufferOutputStream()
    pq.write_table(table, sink, row_group_size=group, compression="snappy",
                   store_decimal_as_integer=True)
    return sink.getvalue().to_pybytes()


def reference_groups(n_files, rows, group, columns, pad):
    """What every item of the pass must hold, in order."""
    for i in range(n_files):
        cols = reference_file(i, rows)
        for lo in range(0, rows, group):
            n = min(group, rows - lo)
            values, valid = {}, {}
            for name in columns:
                vals, ok = cols[name]
                dtype = np.float64 if name == "ratio" else \
                    np.int32 if name in ("key", "date_sk", "price") \
                    else np.int64
                v = np.zeros(pad, dtype)
                v[:n] = np.where(ok[lo:lo + n], vals[lo:lo + n], 0)
                values[name] = v
                if SCHEMA[name][1]:
                    m = np.zeros(pad, bool)
                    m[:n] = ok[lo:lo + n]
                    valid[name] = m
            yield values, valid, n


# -- the deployment, tiny ---------------------------------------------------
@pytest.fixture()
def cluster(tmp_path):
    with LocalCluster(str(tmp_path), num_workers=1, block_size=BLOCK,
                      worker_mem_bytes=64 << 20) as c:
        yield c


@pytest.fixture(autouse=True)
def _cold_planner():
    tplan.clear_caches()
    yield
    tplan.clear_caches()


def _write(cluster, n_files: int, rows: int, group: int):
    fs = cluster.file_system()
    paths = [f"/tpcds/store_sales/part-{i:03d}.parquet"
             for i in range(n_files)]
    for i, path in enumerate(paths):
        fs.write_all(path, parquet_file(i, rows, group),
                     write_type="MUST_CACHE")
    return paths


def _assert_item(item, want) -> None:
    values, valid, n = want
    assert int(np.asarray(item.num_rows)) == n
    assert set(item.values) == set(values)
    assert set(item.valid) == set(valid)
    for name, v in values.items():
        got = np.asarray(item.values[name])
        assert got.dtype == v.dtype, name
        assert np.array_equal(got, v), name
    for name, m in valid.items():
        assert np.array_equal(np.asarray(item.valid[name]), m), name


#: (files, rows a file, rows a row group, columns)
CASES = {
    "q3-projection": (3, 5000, 2000, ["date_sk", "key", "price"]),
    "every-column": (2, 4000, 1500, list(SCHEMA)),
    "one-group-a-file": (3, 3000, 3000, ["price", "amount", "key"]),
}


def _chunk_crosses_a_block(fs, path) -> bool:
    md = pq.read_metadata(pa.BufferReader(fs.read_all(path)))
    for g in range(md.num_row_groups):
        for c in range(md.num_columns):
            col = md.row_group(g).column(c)
            start = min(o for o in (col.dictionary_page_offset,
                                    col.data_page_offset) if o is not None)
            end = start + col.total_compressed_size - 1
            if start // BLOCK != end // BLOCK:
                return True
    return False


@pytest.mark.parametrize("case", sorted(CASES))
def test_row_groups_land_the_reference(cluster, case):
    n_files, rows, group, columns = CASES[case]
    import jax

    paths = _write(cluster, n_files, rows, group)
    fs = cluster.file_system()
    assert any(_chunk_crosses_a_block(fs, p) for p in paths)
    before = _counts()
    yields0 = metrics().snapshot().get("Client.JaxLoaderYields", 0)
    loader = DeviceBlockLoader(fs, paths)
    try:
        with jax.enable_x64(True):  # int64 columns stay int64 on device
            items = list(loader.row_groups(columns))
    finally:
        loader.close()
    want = list(reference_groups(n_files, rows, group, columns, group))
    assert len(items) == len(want)
    assert metrics().snapshot().get("Client.JaxLoaderYields", 0) \
        - yields0 == len(want)
    for item, w in zip(items, want):
        _assert_item(item, w)
    d = _delta(before)
    assert d["Client.StatusBatchCalls"] == 1  # one call for the list
    assert d["Client.TableFooterReads"] == n_files  # one footer a file
    assert d["Client.JaxTableRowGroups"] == len(want)
    assert d["Client.JaxTableRows"] == n_files * rows
    assert d["Client.JaxTablePlanMisses"] == 0
    # the plan fetches the projection, not the file
    assert 0 < d["Client.JaxTableFetchedBytes"] \
        <= d["Client.JaxTableRowGroupBytes"]
    if len(columns) < len(SCHEMA):
        assert d["Client.JaxTableFetchedBytes"] \
            < d["Client.JaxTableRowGroupBytes"]


def test_decimals_land_as_their_unscaled_integers(cluster):
    """decimal(7,2) as int32 cents, decimal(12,2) as int64, negatives
    and the extremes of each precision exact."""
    import jax

    fs = cluster.file_system()
    cents = np.array([0, 1, -1, 9_999_999, -9_999_999, 12_345, -67_890])
    big = np.array([0, 1, -1, 10**12 - 1, -(10**12 - 1), 2**40, -(2**40)])
    valid = np.array([True, True, True, True, True, False, True])
    table = pa.table({
        "p7": _arrow(pa.decimal128(7, 2), cents, valid),
        "p12": _arrow(pa.decimal128(12, 2), big, valid)})
    sink = pa.BufferOutputStream()
    pq.write_table(table, sink, store_decimal_as_integer=True)
    fs.write_all("/dec.parquet", sink.getvalue().to_pybytes())
    loader = DeviceBlockLoader(fs, ["/dec.parquet"])
    try:
        with jax.enable_x64(True):
            (item,) = list(loader.row_groups(["p7", "p12"], rows=9))
    finally:
        loader.close()
    for name, dtype, want in (("p7", np.int32, cents),
                              ("p12", np.int64, big)):
        got = np.asarray(item.values[name])
        assert got.dtype == dtype, name
        assert np.array_equal(got, np.append(np.where(valid, want, 0),
                                             [0, 0])), name
        assert np.array_equal(np.asarray(item.valid[name]),
                              np.append(valid, [False, False])), name


def test_a_short_last_group_pads_to_the_pass_rows(cluster):
    paths = _write(cluster, 2, 500, 200)  # groups of 200, 200, 100
    loader = DeviceBlockLoader(cluster.file_system(), paths)
    try:
        items = list(loader.row_groups(["key", "price"], rows=256))
        with pytest.raises(ValueError, match="more than the pass's 150"):
            list(loader.row_groups(["key"], rows=150))
    finally:
        loader.close()
    want = list(reference_groups(2, 500, 200, ["key", "price"], 256))
    assert [int(np.asarray(i.num_rows)) for i in items] == \
        [200, 200, 100, 200, 200, 100]
    for item, w in zip(items, want):
        _assert_item(item, w)
        # a non-null column's padding is zero and past num_rows
        assert not np.asarray(item.values["key"])[w[2]:].any()


def test_one_device_put_a_row_group_and_the_spans_nest(cluster):
    paths = _write(cluster, 2, 400, 200)
    set_tracing_enabled(True)
    tracer().clear()
    try:
        loader = DeviceBlockLoader(cluster.file_system(), paths)
        try:
            items = list(loader.row_groups(["date_sk", "price"]))
        finally:
            loader.close()
        spans = tracer().snapshot(limit=10_000, prefix="atpu.")
    finally:
        set_tracing_enabled(False)
        tracer().clear()
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    assert len(items) == 4
    assert len(by["atpu.loader.h2d"]) == 4
    reads = {s["span_id"] for s in by["atpu.loader.host_read"]}
    assert len(reads) == 4
    assert len(by["atpu.table.footer"]) == 2  # one a file, cold planner
    for name in ("atpu.table.fetch", "atpu.table.decode",
                 "atpu.table.land"):
        assert len(by[name]) == 4, name
    for name in ("atpu.table.footer", "atpu.table.fetch",
                 "atpu.table.decode", "atpu.table.land"):
        assert {s["parent"] for s in by[name]} <= reads, name
    fetch = by["atpu.table.fetch"][0]["tags"]
    assert int(fetch["bytes"]) > 0 and int(fetch["ranges"]) >= 1
    assert {s["tags"]["row_groups"] for s in by["atpu.table.footer"]} \
        == {"2"}


def _producers() -> list:
    return [t for t in threading.enumerate()
            if t.name.startswith("loader-host-prefetch")]


def test_closing_mid_pass_retires_the_producer(cluster):
    paths = _write(cluster, 3, 600, 100)
    loader = DeviceBlockLoader(cluster.file_system(), paths)
    try:
        it = loader.row_groups(["key"])
        next(it)
        assert len(_producers()) >= 1
        it.close()
        assert loader._producer_pool is None
        assert not [t for t in _producers() if t.is_alive()]
        # the loader is whole: a pass after it reads every row group
        assert len(list(loader.row_groups(["key"]))) == 18
    finally:
        loader.close()


def test_the_first_row_group_leaves_while_the_producer_holds_its_second(
        cluster, monkeypatch):
    from alluxio_tpu.table.reader import PlannedFile

    import jax

    paths = _write(cluster, 2, 400, 100)
    monkeypatch.setattr(PlannedFile, "fetch", PlannedFile.fetch)
    gate, timed_out = held_after_first(PlannedFile, "fetch")
    loader = DeviceBlockLoader(cluster.file_system(), paths)
    try:
        with jax.enable_x64(True):
            it = loader.row_groups(["key", "ticket"])
            items = [next(it)]
            assert timed_out == []
            gate.set()
            items += list(it)
    finally:
        gate.set()
        loader.close()
    want = list(reference_groups(2, 400, 100, ["key", "ticket"], 100))
    assert len(items) == len(want)
    for item, w in zip(items, want):
        _assert_item(item, w)


def test_a_read_after_clear_caches_reads_its_footer_again(cluster):
    paths = _write(cluster, 1, 300, 100)
    fs = cluster.file_system()
    footers = []
    for clear in (False, False, True):
        if clear:
            tplan.clear_caches()
        before = _counts()
        loader = DeviceBlockLoader(fs, paths)
        try:
            assert len(list(loader.row_groups(["key"]))) == 3
        finally:
            loader.close()
        footers.append(_delta(before)["Client.TableFooterReads"])
    # cold: its footer once a pass; warm: none; cleared: once again
    assert footers == [1, 0, 1]


def test_read_columns_returns_what_it_returned_before(cluster):
    """The planned host path, now built on the step the device pass
    shares, against the legacy reader pyarrow drives itself."""
    paths = _write(cluster, 3, 500, 200)
    fs = cluster.file_system()
    legacy_conf = cluster.conf.copy()
    legacy_conf.set(Keys.USER_TABLE_PUSHDOWN_ENABLED, False)
    from alluxio_tpu.client.file_system import FileSystem

    legacy = FileSystem(cluster.master.address, conf=legacy_conf)
    for columns in (["price", "key"], None):
        before = metrics().snapshot().get(
            "Client.TableProjectionPlanMisses", 0)
        got = read_columns(fs, paths, columns=columns)
        assert metrics().snapshot().get(
            "Client.TableProjectionPlanMisses", 0) == before
        want = read_columns(legacy, paths, columns=columns)
        assert got.equals(want)
    legacy.close()


def test_the_suite_projection_row_reads_through_the_planner(cluster):
    """``stress/tpu_suite.py``'s config #4 row, tiny: its reads are the
    planned path (footers fetched by the planner), its row as before."""
    import jax

    from alluxio_tpu.stress.tpu_suite import config4_projection

    before = metrics().snapshot().get("Client.TableFooterReads", 0)
    row = config4_projection(jax, cluster.file_system(), jax.devices()[0],
                             rows_per_part=2_000, partitions=2)
    assert metrics().snapshot()["Client.TableFooterReads"] - before == 2
    assert row["config"] == "4-parquet-projection" and row["value"] > 0
    assert row["full_bytes"] > 0


def test_narrow_decimals_land_as_their_integers(cluster):
    """A file whose footer keeps Arrow's ``decimal32`` / ``decimal64``
    types reads back with them: they land like a ``decimal128``."""
    import jax

    fs = cluster.file_system()
    cents = np.array([0, 1, -1, 9_999_999, -9_999_999, 4_242], np.int32)
    wide = np.array([0, -1, 10**12 - 1, -(10**12 - 1), 5, 7], np.int64)
    valid = np.array([True, True, False, True, True, True])
    bitmap = pa.py_buffer(np.packbits(valid, bitorder="little"))
    table = pa.table({
        "p7": pa.Array.from_buffers(pa.decimal32(7, 2), 6,
                                    [bitmap, pa.py_buffer(cents)], 1),
        "p12": pa.Array.from_buffers(pa.decimal64(12, 2), 6,
                                     [bitmap, pa.py_buffer(wide)], 1)})
    sink = pa.BufferOutputStream()
    pq.write_table(table, sink, store_decimal_as_integer=True)
    fs.write_all("/narrow.parquet", sink.getvalue().to_pybytes())
    assert pq.read_schema(pa.BufferReader(sink.getvalue())).field(
        "p7").type == pa.decimal32(7, 2)
    loader = DeviceBlockLoader(fs, ["/narrow.parquet"])
    try:
        with jax.enable_x64(True):
            (item,) = list(loader.row_groups(["p7", "p12"]))
    finally:
        loader.close()
    for name, dtype, want in (("p7", np.int32, cents),
                              ("p12", np.int64, wide)):
        got = np.asarray(item.values[name])
        assert got.dtype == dtype, name
        assert np.array_equal(got, np.where(valid, want, 0)), name
        assert np.array_equal(np.asarray(item.valid[name]), valid), name


# -- host reads in flight ---------------------------------------------------
class _Reads:
    """Wraps ``PlannedFile``'s constructor and ``fetch`` and
    ``jax_io._land_columns`` to log each row group's read by ``(path,
    row group)``: ``log`` holds ``("fetch", key)`` as a fetch begins and
    ``("land_end", key)`` as a landing ends, in order. ``hold`` maps a
    row group to another: its landing waits until the other's fetch has
    begun (``timed_out`` lists the landings that gave up waiting, which
    a pass that reads one row group at a time makes)."""

    def __init__(self, monkeypatch, hold=None, timeout: float = 10.0):
        from alluxio_tpu.client import jax_io
        from alluxio_tpu.table.reader import PlannedFile

        self.hold = dict(hold or {})
        self.log, self.timed_out = [], []
        self._lock = threading.Lock()
        self._fetched: dict = {}
        local = threading.local()
        init, fetch, land = (PlannedFile.__init__, PlannedFile.fetch,
                             jax_io._land_columns)

        def init_(pf, stream, path, *a, **k):
            init(pf, stream, path, *a, **k)
            pf.test_path = path

        def fetch_(pf, plan, *a, **k):
            local.key = key = (pf.test_path, plan.index)
            self.note("fetch", key)
            self._event(key).set()
            return fetch(pf, plan, *a, **k)

        def land_(table, columns, rows):
            key = local.key
            if key in self.hold and \
                    not self._event(self.hold[key]).wait(timeout):
                self.timed_out.append(key)
            try:
                return land(table, columns, rows)
            finally:
                self.note("land_end", key)

        monkeypatch.setattr(PlannedFile, "__init__", init_)
        monkeypatch.setattr(PlannedFile, "fetch", fetch_)
        monkeypatch.setattr(jax_io, "_land_columns", land_)

    def _event(self, key) -> threading.Event:
        with self._lock:
            return self._fetched.setdefault(key, threading.Event())

    def note(self, what: str, key) -> None:
        with self._lock:
            self.log.append((what, key))

    def reset(self) -> None:
        """A new pass: no fetch has begun."""
        with self._lock:
            self._fetched.clear()
            self.log.clear()
        self.timed_out.clear()


def _overlapped() -> int:
    return metrics().snapshot().get("Client.JaxTableReadsOverlapped", 0)


#: (files, rows a file, rows a row group, the landing held, the fetch it
#: waits for: (file, row group) each)
OVERLAP_CASES = {
    "one-group-a-file": (4, 600, 600, (1, 0), (2, 0)),
    "groups-a-file": (3, 900, 300, (0, 2), (1, 0)),
}


@pytest.mark.parametrize("case", sorted(OVERLAP_CASES))
def test_two_reads_in_flight_land_the_reference_in_order(
        cluster, monkeypatch, case):
    """Prefetch 2: the landing of one row group waits for the next
    one's fetch to begin, which only a second read in flight can do;
    every item is still the reference's, in the pass's order."""
    import jax

    n_files, rows, group, (fa, ga), (fb, gb) = OVERLAP_CASES[case]
    columns = ["date_sk", "key", "price"]
    paths = _write(cluster, n_files, rows, group)
    first, second = (paths[fa], ga), (paths[fb], gb)
    reads = _Reads(monkeypatch, hold={first: second})
    loader = DeviceBlockLoader(cluster.file_system(), paths, prefetch=2)
    before = _counts()
    try:
        with jax.enable_x64(True):
            items = list(loader.row_groups(columns))
    finally:
        loader.close()
    assert reads.timed_out == []
    assert reads.log.index(("fetch", second)) \
        < reads.log.index(("land_end", first))
    want = list(reference_groups(n_files, rows, group, columns, group))
    assert len(items) == len(want)
    for item, w in zip(items, want):
        _assert_item(item, w)
    d = _delta(before)
    assert d["Client.JaxTableRowGroups"] == len(want)
    assert d["Client.TableFooterReads"] == n_files  # one a file
    assert d["Client.JaxTablePlanMisses"] == 0


def test_the_first_row_group_of_every_pass_is_read_alone(cluster,
                                                        monkeypatch):
    """The first item's put is slow; no second fetch begins before it
    is on the queue, in a cold pass and in one on warm lanes."""
    import time

    paths = _write(cluster, 4, 300, 300)
    reads = _Reads(monkeypatch)
    loader = DeviceBlockLoader(cluster.file_system(), paths, prefetch=2)
    real_put, puts = loader._put, []

    def put(q, stop, item):
        if not puts:
            time.sleep(0.3)  # the first item, slow to the queue
        real_put(q, stop, item)
        puts.append(1)
        reads.note("put", len(puts))

    loader._put = put
    try:
        for _ in range(2):
            reads.reset()
            puts.clear()
            assert len(list(loader.row_groups(["key"]))) == 4
            fetches = [i for i, (what, _k) in enumerate(reads.log)
                       if what == "fetch"]
            assert len(fetches) == 4
            assert reads.log.index(("put", 1)) < fetches[1]
    finally:
        loader.close()


def test_a_loader_with_prefetch_0_reads_one_row_group_at_a_time(cluster):
    import jax

    paths = _write(cluster, 3, 600, 300)
    before = _overlapped()
    set_tracing_enabled(True)
    tracer().clear()
    try:
        loader = DeviceBlockLoader(cluster.file_system(), paths,
                                   prefetch=0)
        try:
            with jax.enable_x64(True):
                items = list(loader.row_groups(["key", "ticket"]))
            assert loader._lanes is None
        finally:
            loader.close()
        spans = tracer().snapshot(limit=10_000,
                                  prefix="atpu.loader.host_read")
    finally:
        set_tracing_enabled(False)
        tracer().clear()
    want = list(reference_groups(3, 600, 300, ["key", "ticket"], 300))
    assert len(items) == len(want) == len(spans) == 6
    for item, w in zip(items, want):
        _assert_item(item, w)
    assert _overlapped() == before
    # every read on the producer thread, each ended before the next
    assert len({s["thread"] for s in spans}) == 1
    spans.sort(key=lambda s: s["start_ns"])
    for a, b in zip(spans, spans[1:]):
        assert a["start_ns"] + a["duration_ms"] * 1e6 <= b["start_ns"]


def test_the_overlap_counter_counts_every_row_group_but_a_passs_first(
        cluster, monkeypatch):
    """Files of one row group: each landing from the second to the last
    but one waits for the next fetch, so every read after the first runs
    beside another, on cold lanes and on warm ones."""
    n = 5
    paths = _write(cluster, n, 300, 300)
    reads = _Reads(monkeypatch, hold={(paths[k], 0): (paths[k + 1], 0)
                                      for k in range(1, n - 1)})
    loader = DeviceBlockLoader(cluster.file_system(), paths, prefetch=2)
    try:
        for _ in range(2):
            reads.reset()
            before, groups = _overlapped(), _counts()
            assert len(list(loader.row_groups(["key"]))) == n
            assert reads.timed_out == []
            assert _overlapped() - before == n - 1
            assert _delta(groups)["Client.JaxTableRowGroups"] == n
    finally:
        loader.close()


def test_a_failed_read_fails_the_pass_after_the_items_before_it(cluster):
    """The third row group's read (the second of two in flight) fails
    once the consumer holds the two before it: the pass hands out those
    two, then raises the read's error."""
    import jax

    paths = _write(cluster, 4, 300, 300)
    loader = DeviceBlockLoader(cluster.file_system(), paths, prefetch=2)
    real, gate = loader._stream, threading.Event()

    def stream(path):
        if path == paths[2]:
            gate.wait(10)
            raise OSError("injected read failure")
        return real(path)

    loader._stream = stream
    try:
        with jax.enable_x64(True):
            it = loader.row_groups(["key", "ticket"])
            got = [next(it), next(it)]
            gate.set()
            with pytest.raises(OSError, match="injected read failure"):
                next(it)
    finally:
        gate.set()
        loader.close()
    want = list(reference_groups(2, 300, 300, ["key", "ticket"], 300))
    for item, w in zip(got, want):
        _assert_item(item, w)
    assert not [t for t in _readers() if t.is_alive()]


def _readers() -> list:
    return [t for t in threading.enumerate()
            if t.name.startswith("loader-host-prefetch-read")]


def _held_fetches(monkeypatch):
    """Every fetch after the pass's first waits for the returned
    ``release`` event; ``entered`` is released once a held fetch
    begins."""
    from alluxio_tpu.table.reader import PlannedFile

    real, calls = PlannedFile.fetch, []
    entered, release = threading.Semaphore(0), threading.Event()

    def fetch(pf, *a, **k):
        calls.append(1)
        if len(calls) > 1 and not release.is_set():
            entered.release()
            release.wait(10)
        return real(pf, *a, **k)

    monkeypatch.setattr(PlannedFile, "fetch", fetch)
    return entered, release


def test_closing_with_two_reads_in_flight_retires_every_thread(
        cluster, monkeypatch):
    paths = _write(cluster, 4, 300, 300)
    entered, release = _held_fetches(monkeypatch)
    loader = DeviceBlockLoader(cluster.file_system(), paths, prefetch=2)
    try:
        it = loader.row_groups(["key"])
        next(it)
        # the second and third row groups' reads are both in their fetch
        assert entered.acquire(timeout=10) and entered.acquire(timeout=10)
        assert len([t for t in _readers() if t.is_alive()]) == 2
        threading.Timer(0.2, release.set).start()
        it.close()
        assert loader._producer_pool is None
        assert loader._lanes is None
        assert not [t for t in _producers() if t.is_alive()]
        # the loader is whole: a pass after it reads every row group
        assert len(list(loader.row_groups(["key"]))) == 4
    finally:
        release.set()
        loader.close()


@pytest.mark.parametrize("when", ["after-a-pass", "mid-pass",
                                  "superseded"])
def test_no_stream_of_a_read_lane_outlives_its_lane(cluster, monkeypatch,
                                                    when):
    """The streams the read lanes open stay open across whole passes
    and are closed by ``loader.close()`` (after a pass, or with reads in
    flight) or by a newer pass that retires them."""
    paths = _write(cluster, 4, 300, 300)
    fs = cluster.file_system()
    real_open, opened = fs.open_file, []

    def open_file(*a, **k):
        s = real_open(*a, **k)
        real_close, closed = s.close, []

        def close():
            closed.append(1)
            real_close()

        s.close = close
        opened.append((threading.current_thread(), closed))
        return s

    monkeypatch.setattr(fs, "open_file", open_file)
    entered, release = _held_fetches(monkeypatch)
    if when != "mid-pass":
        release.set()
    loader = DeviceBlockLoader(fs, paths, prefetch=2)
    try:
        it = loader.row_groups(["key"])
        if when == "mid-pass":
            next(it)
            assert entered.acquire(timeout=10)
            threading.Timer(0.2, release.set).start()
        else:
            assert len(list(it)) == 4
        lanes = [(t, c) for t, c in opened
                 if t.name.startswith("loader-host-prefetch-read")]
        assert lanes
        if when == "after-a-pass":
            assert not any(c for _t, c in lanes)  # kept for the next pass
        if when == "superseded":
            it = loader.row_groups(["key"])
            next(it)  # a pass that began after a whole one keeps its lanes
            assert not any(c for _t, c in lanes)
            assert len(list(loader.row_groups(["key"]))) == 4
            lanes = [(t, c) for t, c in lanes if not t.is_alive()]
            assert lanes  # the superseded pass's lanes are gone
        else:
            loader.close()
            assert not [t for t in _readers() if t.is_alive()]
        assert all(c for _t, c in lanes)
    finally:
        release.set()
        loader.close()
