"""Seeded data sets and their ingest. A set is rebuilt shard by shard on
the host from ``--seed``, so the plain reference never reads what the
client wrote and never holds the set in host memory."""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: H x W x C of an ImageNet 64x64 record, and its layout: the benchmark's
#: own copy of ``ops/decode.py``'s ``label(4B little-endian) || pixels``
IMAGE_SHAPE = (64, 64, 3)
LABEL_BYTES = 4
N_CLASSES = 1000


def _rng(seed: int, stream: int = 0):
    # SeedSequence takes any non-negative int: seeds over 2**31 are fine
    return np.random.default_rng([int(seed), stream])


class ByteSet:
    """``n_files`` files of ``file_bytes`` (one block each): a random
    base block, file ``i`` = base + i (mod 256) with its index stamped
    in the first 8 bytes (``chip_smoke.Dataset``'s generator)."""

    def __init__(self, seed: int, n_files: int, file_bytes: int,
                 prefix: str = "/bench/shard") -> None:
        self.n_files = n_files
        self.file_bytes = file_bytes
        self.paths = [f"{prefix}-{i:04d}" for i in range(n_files)]
        self._base = _rng(seed).integers(0, 256, size=file_bytes,
                                         dtype=np.uint8)
        self._base_sum = int(self._base[8:].sum(dtype=np.uint64))
        self._hist = np.bincount(self._base[8:], minlength=256)

    @property
    def total_bytes(self) -> int:
        return self.n_files * self.file_bytes

    def file(self, i: int, out=None) -> np.ndarray:
        out = np.add(self._base, np.uint8(i % 256), out=out)
        out[:8] = np.frombuffer(np.uint64(i).tobytes(), dtype=np.uint8)
        return out

    def byte_sum(self, i: int) -> int:
        """Sum of file ``i``'s bytes mod 2**32, from the base block's
        histogram: no pass over the file."""
        k = i % 256
        vals = (np.arange(256, dtype=np.uint64) + np.uint64(k)) % 256
        body = int((vals * self._hist.astype(np.uint64)).sum())
        head = sum(np.uint64(i).tobytes())
        return (body + head) & 0xFFFFFFFF


class RecordSet:
    """Shards of fixed-size image records padded to ``file_bytes``
    (records never straddle a shard). Pixels: a random base shard,
    shard ``i`` = base + i (mod 256); labels: drawn per shard from the
    seed. Any shard's labels and any record are rebuilt on the host."""

    def __init__(self, seed: int, n_files: int, file_bytes: int,
                 image_shape=IMAGE_SHAPE, prefix: str = "/bench/records"):
        h, w, c = image_shape
        self.image_shape = tuple(image_shape)
        self.record_bytes = LABEL_BYTES + h * w * c
        self.per_file = file_bytes // self.record_bytes
        self.n_files = n_files
        self.file_bytes = file_bytes
        self.seed = seed
        self.paths = [f"{prefix}-{i:04d}" for i in range(n_files)]
        self._base = _rng(seed).integers(
            0, 256, size=(self.per_file, self.record_bytes), dtype=np.uint8)

    @property
    def total_bytes(self) -> int:
        return self.n_files * self.file_bytes

    def labels(self, i: int) -> np.ndarray:
        return _rng(self.seed, 1 + i).integers(
            0, N_CLASSES, size=self.per_file, dtype=np.int32)

    def file(self, i: int, out=None) -> np.ndarray:
        """Shard ``i`` as written: records, then zero padding."""
        if out is None:
            out = np.empty(self.file_bytes, np.uint8)
        used = self.per_file * self.record_bytes
        recs = out[:used].reshape(self.per_file, self.record_bytes)
        np.add(self._base, np.uint8(i % 256), out=recs)
        recs[:, :LABEL_BYTES] = self.labels(i).astype("<i4").view(
            np.uint8).reshape(self.per_file, LABEL_BYTES)
        out[used:] = 0
        return out

    def records(self, i: int) -> np.ndarray:
        """(per_file, record_bytes) uint8 of shard ``i``."""
        used = self.per_file * self.record_bytes
        return self.file(i)[:used].reshape(self.per_file,
                                           self.record_bytes)

    def stream_labels(self, first_record: int, count: int) -> np.ndarray:
        """Labels of ``count`` records of one pass in shard order,
        starting at record ``first_record`` of the pass."""
        out = []
        r = first_record
        while count > 0:
            shard, off = divmod(r, self.per_file)
            take = min(count, self.per_file - off)
            out.append(self.labels(shard)[off:off + take])
            r += take
            count -= take
        return np.concatenate(out)


def ingest(fs, dataset, *, write_type: str, threads: int, runs: int):
    """Write every file of ``dataset`` (``fs.write_all``), ``threads``
    at once, cut into ``runs`` equal runs of files. Returns
    ``[(bytes, wall_s), ...]`` per run; a write that is not
    acknowledged raises."""
    from alluxio_tpu.client.streams import WriteType

    wt = getattr(WriteType, write_type)
    tls = threading.local()

    def put(i: int) -> None:
        if not hasattr(tls, "buf"):  # one buffer a thread, reused
            tls.buf = np.empty(dataset.file_bytes, np.uint8)
        fs.write_all(dataset.paths[i], dataset.file(i, out=tls.buf),
                     write_type=wt)

    n = dataset.n_files
    bounds = [n * r // runs for r in range(runs + 1)]
    out = []
    with ThreadPoolExecutor(threads) as pool:
        for lo, hi in zip(bounds, bounds[1:]):
            t0 = time.perf_counter()
            list(pool.map(put, range(lo, hi)))
            out.append(((hi - lo) * dataset.file_bytes,
                        time.perf_counter() - t0))
    return out
