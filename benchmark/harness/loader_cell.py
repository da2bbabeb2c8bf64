"""What the cells that read through ``DeviceBlockLoader`` share: the
window's loader, a job's cold start, and the two probes of the host ->
device layer. A consumer file subclasses :class:`LoaderCell` and says
what an item is and what a step does with it."""

from __future__ import annotations

import statistics
import time

import numpy as np


class LoaderCell:
    """Subclass contract: ``self.dataset`` in ``__init__``;
    ``items(loader)`` -> the iterator a user calls; ``step(item)``."""

    def __init__(self, *, config, traffic, seed, devices, roles) -> None:
        self.config, self.traffic, self.seed = config, traffic, seed
        self.device = devices[0]
        self.roles = roles
        self.loader = None
        self.fs = None

    # -- the window's loader -------------------------------------------------
    def new_loader(self, fs, paths=None, *, hbm_bytes=None):
        from alluxio_tpu.client.jax_io import DeviceBlockLoader

        return DeviceBlockLoader(
            fs, self.dataset.paths if paths is None else paths,
            device=self.device, prefetch=self.config["prefetch"],
            hbm_bytes=self.config["hbm_bytes"] if hbm_bytes is None
            else hbm_bytes)

    def open(self, fs) -> None:
        self.fs = fs
        self.loader = self.new_loader(fs)

    def inputs(self):
        """Passes back to back, for as long as the caller asks."""
        while True:
            yield from self.items(self.loader)

    def close_window(self) -> dict:
        stats = self.loader.hbm_stats()
        self.loader.close()
        self.loader = None
        return stats

    # -- a job's cold start ----------------------------------------------------
    def cold_start(self) -> dict:
        """New client, new loader over the WHOLE file list (one
        ``get_status`` a path), the first item of the user's iterator,
        on the device. Then, outside the timing, a few ``get_status``
        calls on that client by the benchmark's own clock."""
        import jax

        t0 = time.perf_counter()
        fs = self.roles.file_system()
        t1 = time.perf_counter()
        loader = self.new_loader(fs)
        t2 = time.perf_counter()
        it = self.items(loader)
        try:
            jax.block_until_ready(next(it))
            t3 = time.perf_counter()
        finally:
            it.close()
        status_ms = []
        for path in self.dataset.paths[:16]:
            s0 = time.perf_counter()
            fs.get_status(path)
            status_ms.append((time.perf_counter() - s0) * 1e3)
        loader.close()
        fs.close()
        return {"first_batch_ms": (t3 - t0) * 1e3,
                "loader_ctor_ms": (t2 - t1) * 1e3,
                "get_status_ms": status_ms}

    # -- probes of the host -> device layer (traced runs only) ----------------
    def probe_h2d(self, pairs: int = 8, files_per_pair: int = 4) -> dict:
        """``pairs`` alternating pairs: a raw ``device_put`` of fresh
        heap arrays, then a loader epoch over as many files this client
        has not mapped yet. Returns the median loader/raw ratio of
        bytes/s, and the median time to lease + map one block's host
        view (``host_block``)."""
        import jax

        ds = self.dataset
        fs = self.roles.file_system()
        nbytes = files_per_pair * ds.file_bytes
        ratios, raw_gbps, loader_gbps = [], [], []
        # warm the transfer path once
        jax.device_put(np.zeros(ds.file_bytes, np.uint8),
                       self.device).block_until_ready()
        try:
            for p in range(pairs):
                fresh = [np.full(ds.file_bytes, p + j, np.uint8)
                         for j in range(files_per_pair)]
                t0 = time.perf_counter()
                jax.block_until_ready(
                    [jax.device_put(a, self.device) for a in fresh])
                raw = nbytes / (time.perf_counter() - t0)
                del fresh
                lo = (p * files_per_pair) % (ds.n_files - files_per_pair + 1)
                loader = self.new_loader(
                    fs, ds.paths[lo:lo + files_per_pair], hbm_bytes=0)
                t0 = time.perf_counter()
                jax.block_until_ready(list(loader.epoch()))
                got = nbytes / (time.perf_counter() - t0)
                loader.close()
                ratios.append(got / raw)
                raw_gbps.append(raw / 1e9)
                loader_gbps.append(got / 1e9)
            # files this client has not opened: the tail of the set
            tail = ds.paths[-32:]
            loader = self.new_loader(fs, tail, hbm_bytes=0)
            block_ms = []
            for path in tail:
                t0 = time.perf_counter()
                loader.host_block(path, 0)
                block_ms.append((time.perf_counter() - t0) * 1e3)
            loader.close()
        finally:
            fs.close()
        return {"loader_vs_raw": statistics.median(ratios),
                "raw_gbps": raw_gbps, "loader_gbps": loader_gbps,
                "host_block_ms": statistics.median(block_ms)}
