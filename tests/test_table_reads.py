"""Projection-pushdown read path tests (docs/table_reads.md).

Covers the footer/range planner (tail-read footer fast path, LRU
cache), range coalescing (gap merge, slack boundary, overlap), the
planned pipeline's byte-identity against pyarrow-direct reads across
randomized schemas/projections/row-group sizes, the conf-disabled
legacy path over a real minicluster, and pipeline teardown on a
mid-read transfer error.
"""

import io
import threading

import numpy as np
import pytest

pa = pytest.importorskip("pyarrow")
import pyarrow.parquet as pq  # noqa: E402

from alluxio_tpu.conf import Configuration, Keys
from alluxio_tpu.table import plan as tplan
from alluxio_tpu.table import reader as treader


# ---------------------------------------------------------------- harness
class FakeStream:
    """In-memory stand-in for FileInStream (pread/read/seek/tell)."""

    def __init__(self, data: bytes, counts=None) -> None:
        self._d = data
        self._pos = 0
        self.counts = counts if counts is not None else {}

    def pread(self, off: int, n: int) -> bytes:
        self.counts["preads"] = self.counts.get("preads", 0) + 1
        return self._d[off:off + n]

    def read(self, n: int = -1) -> bytes:
        if n < 0:
            n = len(self._d) - self._pos
        out = self._d[self._pos:self._pos + n]
        self._pos += len(out)
        self.counts["reads"] = self.counts.get("reads", 0) + 1
        return out

    def seek(self, pos: int) -> None:
        self._pos = pos

    def tell(self) -> int:
        return self._pos

    def close(self) -> None:
        pass


class FakeInfo:
    def __init__(self, length: int, file_id: int = 1,
                 mtime: int = 1000) -> None:
        self.length = length
        self.file_id = file_id
        self.last_modification_time_ms = mtime
        self.folder = False


class FakeFs:
    def __init__(self, files: dict, conf=None) -> None:
        self._files = files
        self.conf = conf if conf is not None else Configuration()
        self.counts = {}

    def get_status(self, path: str) -> FakeInfo:
        return FakeInfo(len(self._files[path]), file_id=hash(path) & 0xFF)

    def open_file(self, path: str, **kw) -> FakeStream:
        return FakeStream(self._files[path], self.counts)


def _table(rng, rows: int, num_cols: int, str_cols: int):
    cols = {}
    for i in range(num_cols):
        cols[f"c{i}"] = rng.integers(0, 1 << 20, size=rows,
                                     dtype=np.int64)
    for i in range(str_cols):
        cols[f"s{i}"] = [f"v{i}-{j % 37}" for j in range(rows)]
    return pa.table(cols)


def _parquet(table, row_group_size: int, compression="none") -> bytes:
    sink = io.BytesIO()
    pq.write_table(table, sink, row_group_size=row_group_size,
                   compression=compression)
    return sink.getvalue()


@pytest.fixture(autouse=True)
def _fresh_caches():
    tplan.clear_caches()
    yield


# ------------------------------------------------------------- coalescing
class TestCoalesce:
    def test_gap_merge_under_slack(self):
        assert tplan.coalesce([(0, 10), (15, 10)], slack=5) == [(0, 25)]

    def test_slack_boundary_not_crossed(self):
        # gap of 6 > slack 5: stays two reads
        assert tplan.coalesce([(0, 10), (16, 10)], slack=5) == \
            [(0, 10), (16, 10)]

    def test_zero_slack_merges_only_touching(self):
        assert tplan.coalesce([(0, 10), (10, 5), (21, 4)]) == \
            [(0, 15), (21, 4)]

    def test_overlapping_ranges_merge(self):
        assert tplan.coalesce([(0, 20), (10, 5), (12, 30)]) == [(0, 42)]

    def test_unsorted_input_and_empties(self):
        assert tplan.coalesce([(30, 4), (0, 10), (5, 0)], slack=0) == \
            [(0, 10), (30, 4)]

    def test_contained_range_keeps_outer_length(self):
        assert tplan.coalesce([(0, 100), (10, 5)]) == [(0, 100)]


# ------------------------------------------------------------ footer path
class TestFooter:
    def test_single_tail_read_when_footer_fits(self):
        t = _table(np.random.default_rng(0), 1000, 4, 1)
        data = _parquet(t, 500)
        calls = []

        def pread(off, n):
            calls.append((off, n))
            return data[off:off + n]

        f = tplan.read_footer(pread, len(data))
        assert len(calls) == 1  # one tail read, no probe-seeks
        assert f.metadata.num_rows == 1000
        assert f.tail_offset + len(f.tail) == len(data)

    def test_second_exact_read_when_footer_outgrows_guess(self):
        t = _table(np.random.default_rng(0), 100, 40, 4)
        data = _parquet(t, 10)  # many row groups -> fat footer
        calls = []

        def pread(off, n):
            calls.append((off, n))
            return data[off:off + n]

        f = tplan.read_footer(pread, len(data), guess_bytes=256)
        assert len(calls) == 2
        # second read is exactly footer + trailer, from its true start
        footer_len = int.from_bytes(data[-8:-4], "little")
        assert calls[1] == (len(data) - footer_len - 8, footer_len + 8)
        assert f.metadata.num_columns == 44

    def test_not_parquet_raises_plan_error(self):
        junk = b"x" * 64
        with pytest.raises(tplan.ParquetPlanError):
            tplan.read_footer(lambda o, n: junk[o:o + n], len(junk))

    def test_too_short_raises_plan_error(self):
        with pytest.raises(tplan.ParquetPlanError):
            tplan.read_footer(lambda o, n: b"", 4)

    def test_cache_hits_on_same_version_misses_on_new(self):
        t = _table(np.random.default_rng(0), 200, 3, 0)
        data = _parquet(t, 100)
        info = FakeInfo(len(data))
        reads = []

        def pread(off, n):
            reads.append(n)
            return data[off:off + n]

        f1 = tplan.cached_footer(pread, "/p", info)
        f2 = tplan.cached_footer(pread, "/p", info)
        assert f1 is f2 and len(reads) == 1
        info2 = FakeInfo(len(data), mtime=2000)  # rewritten file
        tplan.cached_footer(pread, "/p", info2)
        assert len(reads) == 2

    def test_cache_capacity_bounded(self):
        c = tplan.FooterCache(max_entries=2)
        for i in range(5):
            c.put((i,), object())
        assert c.size() == 2


# ----------------------------------------------------------- plan content
class TestPlan:
    def test_ranges_cover_exactly_projected_chunks(self):
        t = _table(np.random.default_rng(1), 3000, 5, 2)
        data = _parquet(t, 1000)
        md = pq.read_metadata(pa.BufferReader(data))
        plans = tplan.plan_row_groups(md, ["c1", "s0"])
        assert len(plans) == 3
        for p in plans:
            assert sorted(r.column for r in p.ranges) == ["c1", "s0"]
            assert p.projected_bytes == sum(r.length for r in p.ranges)
            # coalesced reads cover every exact range
            for r in p.ranges:
                assert any(off <= r.offset and
                           r.offset + r.length <= off + n
                           for off, n in p.reads)

    def test_none_projection_plans_every_column(self):
        t = _table(np.random.default_rng(1), 500, 3, 1)
        md = pq.read_metadata(pa.BufferReader(_parquet(t, 500)))
        (p,) = tplan.plan_row_groups(md, None)
        assert len(p.ranges) == 4

    def test_unknown_column_ignored_at_plan_time(self):
        t = _table(np.random.default_rng(1), 500, 3, 0)
        md = pq.read_metadata(pa.BufferReader(_parquet(t, 500)))
        (p,) = tplan.plan_row_groups(md, ["c0", "nope"])
        assert [r.column for r in p.ranges] == ["c0"]


# ------------------------------------------------- planned read identity
class TestPlannedByteIdentity:
    @pytest.mark.parametrize("seed", range(6))
    def test_property_sweep_random_schema_projection_rg(self, seed):
        rng = np.random.default_rng(seed)
        rows = int(rng.integers(100, 4000))
        num_cols = int(rng.integers(1, 12))
        str_cols = int(rng.integers(0, 4))
        rg = int(rng.integers(64, max(65, rows + 1)))
        compression = ["none", "snappy"][seed % 2]
        t = _table(rng, rows, num_cols, str_cols)
        data = _parquet(t, rg, compression=compression)
        names = t.column_names
        k = int(rng.integers(1, len(names) + 1))
        proj = list(rng.choice(names, size=k, replace=False))
        fs = FakeFs({"/f": data})
        out = treader.read_columns(fs, ["/f"], columns=proj)
        assert out.equals(t.select(proj))

    def test_full_scan_and_multi_file_identity(self):
        rng = np.random.default_rng(7)
        t1, t2 = _table(rng, 900, 4, 1), _table(rng, 400, 4, 1)
        fs = FakeFs({"/a": _parquet(t1, 256), "/b": _parquet(t2, 256)})
        out = treader.read_columns(fs, ["/a", "/b"])
        assert out.equals(pa.concat_tables([t1, t2]))

    def test_planned_issues_fewer_preads_than_chunks(self):
        rng = np.random.default_rng(8)
        t = _table(rng, 8000, 10, 0)
        fs = FakeFs({"/f": _parquet(t, 1000)})  # 8 rgs x 10 cols
        out = treader.read_columns(fs, ["/f"],
                                   columns=["c0", "c1", "c2"])
        assert out.equals(t.select(["c0", "c1", "c2"]))
        # 24 projected chunks; coalescing + footer fast path keep the
        # transfer round trips well under one per chunk
        assert fs.counts.get("preads", 0) < 24

    def test_unknown_column_matches_legacy_semantics(self):
        # pyarrow ignores unknown names (empty-column table); the
        # planned path must do exactly what the legacy path does
        rng = np.random.default_rng(9)
        data = _parquet(_table(rng, 100, 2, 0), 100)
        planned = treader.read_columns(FakeFs({"/f": data}), ["/f"],
                                       columns=["missing"])
        legacy = treader.read_columns(
            FakeFs({"/f": data}, conf=Configuration(
                {Keys.USER_TABLE_PUSHDOWN_ENABLED: "false"})),
            ["/f"], columns=["missing"])
        assert planned.equals(legacy)

    def test_disabled_conf_uses_legacy_path(self):
        rng = np.random.default_rng(10)
        t = _table(rng, 500, 3, 1)
        conf = Configuration(
            {Keys.USER_TABLE_PUSHDOWN_ENABLED: "false"})
        fs = FakeFs({"/f": _parquet(t, 250)}, conf=conf)
        out = treader.read_columns(fs, ["/f"], columns=["c1"])
        assert out.equals(t.select(["c1"]))
        # legacy path streams through read(), not planned preads
        assert fs.counts.get("reads", 0) > 0

    def test_non_parquet_falls_back_to_legacy_error(self):
        fs = FakeFs({"/junk": b"not parquet at all" * 10})
        with pytest.raises(Exception) as planned_err:
            treader.read_columns(fs, ["/junk"])
        fs2 = FakeFs({"/junk": b"not parquet at all" * 10},
                     conf=Configuration(
                         {Keys.USER_TABLE_PUSHDOWN_ENABLED: "false"}))
        with pytest.raises(Exception) as legacy_err:
            treader.read_columns(fs2, ["/junk"])
        assert type(planned_err.value) is type(legacy_err.value)


# ------------------------------------------------------- range-cache file
class TestRangeCachedFile:
    def test_miss_falls_through_and_counts(self):
        data = bytes(range(256)) * 16
        stream = FakeStream(data)
        src = treader._RangeCachedFile(stream, len(data),
                                       threading.Lock())
        src.install(100, data[100:200])
        src.seek(100)
        assert src.read(100) == data[100:200]
        assert stream.counts.get("preads", 0) == 0  # cache hit
        src.seek(0)
        assert src.read(50) == data[:50]  # miss -> underlying pread
        assert stream.counts["preads"] == 1

    def test_miss_read_stops_at_next_staged_buffer(self):
        data = bytes(range(256)) * 4
        stream = FakeStream(data)
        src = treader._RangeCachedFile(stream, len(data),
                                       threading.Lock())
        src.install(64, data[64:128])
        src.seek(0)
        assert src.read(200) == data[:200]  # gap + staged + gap
        # the staged slice was served from memory, not refetched

    def test_drop_releases_buffers(self):
        data = b"z" * 1024
        src = treader._RangeCachedFile(FakeStream(data), len(data),
                                       threading.Lock())
        src.install(0, data[:512])
        src.drop([0])
        src.seek(0)
        src.read(10)
        assert src._s.counts["preads"] == 1


# -------------------------------------------------------- pipeline errors
class TestPipelineTeardown:
    def test_mid_read_transfer_error_propagates_and_joins(self):
        rng = np.random.default_rng(11)
        t = _table(rng, 4000, 6, 0)
        data = _parquet(t, 500)  # 8 row groups

        class FailingStream(FakeStream):
            def __init__(self, data):
                super().__init__(data)
                self.calls = 0

            def pread(self, off, n):
                self.calls += 1
                if self.calls > 3:  # footer + first fetches succeed
                    raise RuntimeError("worker lost mid-read")
                return super().pread(off, n)

        class FailingFs(FakeFs):
            def open_file(self, path, **kw):
                return FailingStream(self._files[path])

        fs = FailingFs({"/f": data})
        before = threading.active_count()
        with pytest.raises(RuntimeError, match="worker lost"):
            treader._PlannedRead(fs, "/f", ["c0", "c1"], fs.conf).run()
        # the shared fetch pool survives; no stray per-read threads leak
        assert threading.active_count() <= before + 4

        # and the reader still works for the next (healthy) file
        ok = FakeFs({"/f": data})
        out = treader.read_columns(ok, ["/f"], columns=["c0"])
        assert out.equals(t.select(["c0"]))

    def test_decode_error_does_not_hang(self):
        rng = np.random.default_rng(12)
        t = _table(rng, 2000, 4, 0)
        data = bytearray(_parquet(t, 250))
        md = pq.read_metadata(pa.BufferReader(bytes(data)))
        # corrupt one mid-file data page so decode (not planning) fails
        col = md.row_group(4).column(0)
        off = col.data_page_offset
        data[off + 20:off + 36] = b"\xff" * 16
        fs = FakeFs({"/f": bytes(data)})
        with pytest.raises(Exception):
            treader.read_columns(fs, ["/f"], columns=["c0"])


# --------------------------------------------------------- minicluster e2e
@pytest.fixture()
def cluster(tmp_path):
    from alluxio_tpu.minicluster.local_cluster import LocalCluster

    with LocalCluster(str(tmp_path), num_workers=1) as c:
        yield c


class TestMinicluster:
    def test_disabled_conf_byte_identity_e2e(self, cluster):
        fs = cluster.file_system()
        rng = np.random.default_rng(13)
        t = _table(rng, 5000, 8, 2)
        fs.write_all("/tbl/part-0.parquet", _parquet(t, 1024))
        proj = ["c2", "c5", "s1"]

        fs.conf.set(Keys.USER_TABLE_PUSHDOWN_ENABLED, True)
        planned = treader.read_columns(fs, ["/tbl/part-0.parquet"],
                                       columns=proj)
        fs.conf.set(Keys.USER_TABLE_PUSHDOWN_ENABLED, False)
        legacy = treader.read_columns(fs, ["/tbl/part-0.parquet"],
                                      columns=proj)
        fs.conf.set(Keys.USER_TABLE_PUSHDOWN_ENABLED, True)

        assert planned.equals(legacy)
        assert planned.equals(t.select(proj))

    def test_planned_multi_file_e2e(self, cluster):
        fs = cluster.file_system()
        rng = np.random.default_rng(14)
        parts = [_table(rng, 1500, 5, 1) for _ in range(3)]
        for i, t in enumerate(parts):
            fs.write_all(f"/tbl2/part-{i}.parquet", _parquet(t, 512))
        out = treader.read_columns(
            fs, [f"/tbl2/part-{i}.parquet" for i in range(3)],
            columns=["c0", "s0"])
        assert out.equals(
            pa.concat_tables([t.select(["c0", "s0"]) for t in parts]))
