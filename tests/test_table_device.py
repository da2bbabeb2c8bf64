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
