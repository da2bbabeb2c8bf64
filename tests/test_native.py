"""Native (C++) frame scanner tests: zlib/Python parity, torn-tail
semantics, and the journal integration paths."""

import mmap
import os
import resource
import struct
import tempfile
import zlib

import numpy as np
import pytest

from alluxio_tpu import native

H = struct.Struct("<II")


def _frame(body: bytes) -> bytes:
    return H.pack(len(body), zlib.crc32(body)) + body


@pytest.fixture(scope="module")
def lib():
    handle = native.lib()
    if handle is None:
        pytest.skip("no native toolchain")
    return handle


class TestNativeScanner:
    def test_crc32_matches_zlib(self, lib):
        for payload in (b"", b"x", b"abc" * 1000, os.urandom(65536)):
            assert native.crc32(payload) == zlib.crc32(payload)

    def test_scan_parity_and_offsets(self, lib):
        bodies = [os.urandom(1 + i % 50) for i in range(200)]
        buf = b"".join(_frame(b) for b in bodies)
        frames, end = native.scan_frames(buf)
        assert len(frames) == 200 and end == len(buf)
        for (off, ln), body in zip(frames, bodies):
            assert buf[off:off + ln] == body

    def test_torn_tail_stops_scan(self, lib):
        good = _frame(b"alpha") + _frame(b"beta")
        torn = good + H.pack(100, 999) + b"tiny"
        frames, end = native.scan_frames(torn)
        assert len(frames) == 2 and end == len(good)

    def test_zero_padding_guard(self, lib):
        good = _frame(b"alpha")
        frames, end = native.scan_frames(good + b"\x00" * 32)
        assert len(frames) == 1 and end == len(good)

    def test_crc_mismatch_stops_scan(self, lib):
        buf = bytearray(_frame(b"alpha") + _frame(b"beta"))
        buf[len(_frame(b"alpha")) + 8] ^= 0xFF  # corrupt beta's body
        frames, _ = native.scan_frames(bytes(buf))
        assert len(frames) == 1

    def test_empty_and_header_only(self, lib):
        assert native.scan_frames(b"") == ([], 0)
        frames, end = native.scan_frames(b"\x01\x02\x03")  # short header
        assert frames == [] and end == 0

    def test_chunked_scan_crosses_chunk_boundary(self, lib):
        from alluxio_tpu.native import _SCAN_CHUNK

        count = _SCAN_CHUNK + 17
        body = b"ab"
        buf = _frame(body) * count
        frames, end = native.scan_frames(buf)
        assert len(frames) == count and end == len(buf)

    def test_scan_is_zero_copy_on_bytes(self, lib):
        # bytes input must use the internal buffer directly (no
        # from_buffer_copy path) — verify via a large buffer round trip
        buf = _frame(os.urandom(100)) * 500
        frames, end = native.scan_frames(buf)
        assert len(frames) == 500 and end == len(buf)

    def test_prefault_readonly_numpy_view(self, lib):
        raw = os.urandom(1 << 16)
        arr = np.frombuffer(raw, dtype=np.uint8)  # readonly view
        assert not arr.flags.writeable
        assert native.prefault(arr) in native.PREFAULT_MODES

    def test_prefault_runs(self, lib):
        arr = np.frombuffer(os.urandom(1 << 16), dtype=np.uint8).copy()
        assert native.prefault(arr) in native.PREFAULT_MODES


PAGE = os.sysconf("SC_PAGESIZE")
MAPPED = 2048 * PAGE  # fault-around maps 16 pages a trap: 128 traps cold


def _minflt() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


@pytest.fixture()
def fresh_mapping(tmp_path):
    """Read-only shared mappings of a tmpfs file another handle wrote:
    what the SHM route hands the loader. Each call maps anew, so no page
    of it is in this process's page table yet."""
    shm = "/dev/shm"
    where = shm if os.access(shm, os.W_OK) else str(tmp_path)
    fd, path = tempfile.mkstemp(prefix="atpu_test_prefault_", dir=where)
    with os.fdopen(fd, "wb") as f:
        f.write(os.urandom(MAPPED))

    def make():
        with open(path, "rb") as f:
            m = mmap.mmap(f.fileno(), 0, prot=mmap.PROT_READ)
        # the view pins the map: it is unmapped when the view dies
        return np.frombuffer(m, dtype=np.uint8)

    yield make
    os.unlink(path)


def _faults_of_reading(view) -> int:
    before = _minflt()
    _ = int(view[::PAGE].sum()) + int(view[-1])
    return _minflt() - before


class TestPrefault:
    """``atpu_prefault``'s rungs: which one reports, and that each
    leaves the pages present (no fault when they are read after)."""

    @pytest.fixture(scope="class")
    def top(self, lib):
        # what this kernel gives a plain range: populate on Linux
        # >= 5.14, lock or touch below that
        mode = native.prefault(np.zeros(PAGE, np.uint8))
        assert mode in native.PREFAULT_MODES
        return mode

    @pytest.mark.parametrize("kind", [
        "heap-bytes", "writable-ndarray", "file-mapping",
        "mid-page-view", "one-byte", "odd-length", "empty"])
    def test_reports_its_mode_and_leaves_the_pages_present(
            self, lib, top, fresh_mapping, kind):
        view = {
            "heap-bytes": lambda: os.urandom(3 * PAGE + 17),
            "writable-ndarray": lambda: np.ones(5 * PAGE, np.uint8),
            "file-mapping": fresh_mapping,
            "mid-page-view": lambda: fresh_mapping()[PAGE + 100:-333],
            "one-byte": lambda: fresh_mapping()[7 * PAGE + 5:][:1],
            "odd-length": lambda: fresh_mapping()[:MAPPED - PAGE - 1],
            "empty": lambda: fresh_mapping()[:0],
        }[kind]()
        mode = native.prefault(view)
        if kind == "empty":
            assert mode == "touch"  # nothing to ask the kernel for
            return
        assert mode == top
        if kind not in ("heap-bytes", "writable-ndarray"):
            # a fresh mapping: cold it takes a trap every 16 pages
            assert _faults_of_reading(view) <= 2

    def test_a_fresh_mapping_does_fault_without_it(self, fresh_mapping):
        # the reading the residency assertions stand on (a file system
        # with large folios maps far more than 16 pages a trap)
        assert _faults_of_reading(fresh_mapping()) >= 3

    @pytest.mark.parametrize("first,allowed", [
        ("lock", {"lock", "touch"}),  # touch where RLIMIT_MEMLOCK says no
        ("touch", {"touch"})])
    def test_the_fallback_rungs_still_run(self, lib, fresh_mapping, first,
                                          allowed):
        view = fresh_mapping()
        assert native.prefault(view, first) in allowed
        assert _faults_of_reading(view) <= 2


class TestConcurrency:
    def test_parallel_scans_agree(self, lib):
        """The ctypes boundary releases the GIL: concurrent scans (e.g.
        several minicluster roles recovering at once) must all see the
        same frames — guards the CRC-table static-init discipline."""
        import threading

        bodies = [os.urandom(64) for _ in range(500)]
        buf = b"".join(_frame(b) for b in bodies)
        results, errors = [], []

        def scan():
            try:
                results.append(native.scan_frames(buf))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=scan) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        assert all(r == results[0] for r in results)
        assert len(results[0][0]) == 500


class TestJournalIntegration:
    def test_decode_stream_uses_validated_frames(self, tmp_path, lib):
        from alluxio_tpu.journal.format import JournalEntry

        p = tmp_path / "journal.bin"
        entries = [JournalEntry(i, "inode_create", {"i": i})
                   for i in range(50)]
        blob = b"".join(e.encode() for e in entries)
        p.write_bytes(blob + b"\x00" * 16)  # zero-padded tail
        with open(p, "rb") as f:
            got = list(JournalEntry.decode_stream(f))
        assert [e.sequence for e in got] == list(range(50))

    def test_raft_log_open_native_scan(self, tmp_path, lib):
        from alluxio_tpu.journal.format import JournalEntry
        from alluxio_tpu.journal.raft import RaftLog, RaftRecord

        log = RaftLog(str(tmp_path / "raft"))
        log.open()
        for i in range(1, 21):
            log.append(RaftRecord(
                1, i, [JournalEntry(i, "inode_create", {"i": i})]))
        log.close()
        # torn tail: append garbage after valid frames
        with open(log._log_path, "ab") as f:
            f.write(H.pack(1000, 42) + b"torn")
        log2 = RaftLog(str(tmp_path / "raft"))
        log2.open()
        assert log2.last_index == 20
        assert [r.index for r in log2.records] == list(range(1, 21))
        log2.close()


class TestBuildKey:
    """The library is trusted by what it was built from, never by its
    clock: a stale build stays stale however new its mtime."""

    @pytest.fixture()
    def tree(self, lib, tmp_path, monkeypatch):
        import shutil

        for src in native._sources():
            shutil.copy(src, tmp_path)
        monkeypatch.setattr(native, "_DIR", str(tmp_path))
        return tmp_path

    def test_edit_rebuilds_even_when_the_old_library_is_newer(self, tree):
        first = native._build()
        assert native._build() == first  # same inputs: no rebuild
        with open(tree / "framing.cpp", "a") as f:
            f.write("\n// edited\n")
        future = os.path.getmtime(first) + 3600
        os.utime(first, (future, future))  # newer than every source
        second = native._build()
        assert second != first and os.path.exists(second)
        assert not os.path.exists(first)  # other build keys are removed

    def test_a_library_copied_in_is_never_loaded(self, tree):
        (tree / "_libatpu_native.so").write_bytes(b"not built here")
        (tree / "_libatpu_native.0123456789abcdef.so").write_bytes(b"x")
        built = native._build()
        assert os.path.getsize(built) > 1000
        assert [p.name for p in tree.glob("*.so")] == \
            [os.path.basename(built)]

    def test_compile_failure_with_a_toolchain_is_an_error(self, tree):
        (tree / "broken.cpp").write_text("this is not C++\n")
        with pytest.raises(native.NativeBuildError):
            native._build()
