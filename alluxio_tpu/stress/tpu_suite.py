"""On-device stages for BASELINE configs #2–#5, run by ``bench.py``
after the headline (config #1) on the SAME live cluster + device.

Each stage emits one structured row with an explicit ``vs_baseline``,
graded against a ceiling the same stage measures adjacently:

  #2 random-4k    achieved 4k-record read->batch->HBM rate vs the raw
                  mmap+device_put ceiling measured adjacently (target
                  >=0.5x: batching small records costs at most half the
                  raw sequential path; the FUSE analogue in the
                  reference pays a kernel crossing per read instead)
  #3 prefetch     distributedLoad fan-out into 2 workers then stream to
                  HBM vs streaming a pre-warmed set (target >=0.7x: the
                  load job must not leave the tiers colder than a plain
                  warm-up)
  #4 projection   3-of-23-column Parquet read into device arrays vs the
                  full-scan wall time (target: speedup >= 3x, the
                  byte-selectivity bound from BENCH_SUITE history)
  #5 write-evict  CACHE_THROUGH ingest under 2x memory pressure with
                  LRFU eviction vs the unpressured cold-write rate of
                  config #1 (target >=0.5x: eviction + UFS write-through
                  may halve ingest but must not collapse it)

Reference analogues: ``AlluxioFuseFileSystem.java:52-55`` random reads,
``LoadDefinition.java:65`` fan-out, ``AlluxioCatalog.java:55`` +
transform path, ``TieredBlockStore.java:85`` + ``LRFUAnnotator.java:29``.
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, List

import numpy as np


def log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def _row(config: str, metric: str, value: float, unit: str,
         vs_baseline: float, **extra) -> Dict:
    row = {"config": config, "metric": metric,
           "value": round(value, 3), "unit": unit,
           "vs_baseline": round(vs_baseline, 3), **extra}
    log("TPU-CONFIG " + json.dumps(row, sort_keys=True))
    return row


def config2_random_4k(jax, fs, device, *, shard_bytes: int,
                      num_shards: int = 4, reads: int = 4096,
                      batch: int = 256) -> Dict:
    """Random 4k reads from the warm host tier, batched into HBM through
    the loader's sample-grain entry (``DeviceBlockLoader.windows``): a
    batch of ``batch`` windows is one ``device_put``."""
    from alluxio_tpu.client.jax_io import DeviceBlockLoader
    from alluxio_tpu.client.streams import WriteType

    rng = np.random.default_rng(7)
    paths = []
    for i in range(num_shards):
        p = f"/bench/r4k-{i}"
        fs.write_all(p, rng.integers(0, 255, size=shard_bytes,
                                     dtype=np.uint8).tobytes(),
                     write_type=WriteType.MUST_CACHE)
        paths.append(p)
    # ceiling: sequential mmap of one shard + one device_put of it
    t0 = time.monotonic()
    blob = fs.read_all(paths[0])
    arr = np.frombuffer(blob, dtype=np.uint8)
    jax.device_put(arr, device).block_until_ready()
    ceil_rate = shard_bytes / (time.monotonic() - t0)

    offsets = rng.integers(0, shard_bytes - 4096, size=reads)
    shards = rng.integers(0, num_shards, size=reads)
    rows = np.stack([shards, offsets], axis=1)
    loader = DeviceBlockLoader(fs, paths, device=device)
    try:
        t0 = time.monotonic()
        devs = list(loader.windows(
            (rows[i:i + batch] for i in range(0, reads, batch)),
            window_bytes=4096))
        jax.block_until_ready(devs)
        dt = time.monotonic() - t0
    finally:
        loader.close()
    rate = reads * 4096 / dt
    return _row("2-random-4k",
                "random 4k reads batched into HBM", rate / 1e6, "MB/s",
                (rate / ceil_rate) / 0.5,
                ops_per_s=round(reads / dt, 1),
                ceiling_mb_per_s=round(ceil_rate / 1e6, 2),
                achieved_vs_ceiling=round(rate / ceil_rate, 3))


def config3_prefetch(jax, device, *, file_bytes: int,
                     num_files: int = 4, num_workers: int = 2) -> Dict:
    """DistributedLoad fan-out on its own multi-worker cluster, then
    stream the prefetched set into HBM (the cold corpus leg mirrors
    ``stress/prefetch_bench.py``; this adds the device leg)."""
    from alluxio_tpu.client.streams import WriteType
    from alluxio_tpu.conf import Keys
    from alluxio_tpu.stress.cluster import bench_cluster

    rng = np.random.default_rng(11)
    total = num_files * file_bytes
    with bench_cluster(None, num_workers=num_workers,
                       block_size=4 << 20,
                       worker_mem_bytes=total + (128 << 20),
                       start_job_service=True,
                       start_worker_heartbeats=True,
                       conf_overrides={
                           Keys.WORKER_BLOCK_HEARTBEAT_INTERVAL: "50ms",
                       }) as (fs, cluster):
        for i in range(num_files):
            fs.write_all(f"/pf/f-{i}",
                         rng.integers(0, 255, size=file_bytes,
                                      dtype=np.uint8).tobytes(),
                         write_type=WriteType.CACHE_THROUGH)
        # warm reference: cached set streamed to HBM
        t0 = time.monotonic()
        ref = [jax.device_put(
            np.frombuffer(fs.read_all(f"/pf/f-{i}"), dtype=np.uint8),
            device) for i in range(num_files)]
        jax.block_until_ready(ref)
        ref_rate = total / (time.monotonic() - t0)
        del ref
        # make the corpus cold, fan the load out, re-stream
        for i in range(num_files):
            fs.free(f"/pf/f-{i}", forced=True)
        job_client = cluster.job_client()
        t0 = time.monotonic()
        job_id = job_client.run({"type": "load", "path": "/pf",
                                 "replication": 1})
        info = job_client.wait_for_job(job_id, timeout_s=300.0)
        t_load = time.monotonic() - t0
        if info.status != "COMPLETED":
            raise RuntimeError(f"load job {info.status}: "
                               f"{info.error_message}")
        t0 = time.monotonic()
        out = [jax.device_put(
            np.frombuffer(fs.read_all(f"/pf/f-{i}"), dtype=np.uint8),
            device) for i in range(num_files)]
        jax.block_until_ready(out)
        rate = total / (time.monotonic() - t0)
        del out
        return _row("3-distributed-prefetch",
                    "post-prefetch stream to HBM", rate / 1e6, "MB/s",
                    (rate / ref_rate) / 0.7,
                    load_seconds=round(t_load, 2),
                    prefetch_mb_per_s=round(total / t_load / 1e6, 2),
                    warm_reference_mb_per_s=round(ref_rate / 1e6, 2))


def config4_projection(jax, fs, device, *, rows_per_part: int = 30_000,
                       partitions: int = 2) -> Dict:
    """Parquet column projection into device arrays vs full scan."""
    import io

    import pyarrow as pa
    import pyarrow.parquet as pq

    from alluxio_tpu.table.reader import open_parquet

    rng = np.random.default_rng(13)
    cols = {f"c{i}": rng.standard_normal(rows_per_part).astype(np.float32)
            for i in range(20)}
    cols["label"] = rng.integers(0, 1000, size=rows_per_part,
                                 dtype=np.int32)
    cols["id"] = np.arange(rows_per_part, dtype=np.int64)
    cols["weight"] = rng.standard_normal(rows_per_part).astype(np.float32)
    table = pa.table(cols)
    sink = io.BytesIO()
    pq.write_table(table, sink)
    blob = sink.getvalue()
    paths = []
    for p in range(partitions):
        path = f"/bench/proj-{p}.parquet"
        fs.write_all(path, blob)
        paths.append(path)
    want = ["c0", "label", "weight"]
    # warm footers
    for p in paths:
        open_parquet(fs, p)
    t0 = time.monotonic()
    full = [open_parquet(fs, p).read() for p in paths]
    t_full = time.monotonic() - t0
    n_full = sum(t.nbytes for t in full)
    del full
    t0 = time.monotonic()
    devs = []
    for p in paths:
        t = open_parquet(fs, p).read(columns=want)
        for name in want:
            devs.append(jax.device_put(
                np.ascontiguousarray(t.column(name).to_numpy()), device))
    jax.block_until_ready(devs)
    t_proj = time.monotonic() - t0
    speedup = t_full / t_proj if t_proj > 0 else 0.0
    return _row("4-parquet-projection",
                "3-of-23-column projection speedup into HBM", speedup,
                "x", speedup / 3.0,
                full_scan_s=round(t_full, 3),
                projection_s=round(t_proj, 3),
                full_bytes=n_full)


def config5_write_eviction(*, cold_write_rate: float) -> Dict:
    """CACHE_THROUGH ingest under memory pressure (dataset ~3x the MEM
    tier, LRFU, SSD spill): reuses the pressured-cluster write bench
    (``stress/write_bench.py``) and grades its ingest against the
    unpressured cold-write rate config #1 measured."""
    from alluxio_tpu.stress import write_bench

    r = write_bench.run()
    rate = r.metrics["ingest_mb_per_s"] * 1e6
    return _row("5-write-through-eviction",
                "CACHE_THROUGH ingest under memory pressure",
                rate / 1e6, "MB/s",
                (rate / cold_write_rate) / 0.5 if cold_write_rate else 0.0,
                unpressured_cold_write_mb_per_s=round(
                    cold_write_rate / 1e6, 2),
                time_to_durable_s=r.metrics.get("time_to_durable_s"),
                tier_used_bytes=r.metrics.get("tier_used_bytes"))


def run_all(jax, fs, device, *, shard_bytes: int,
            cold_write_rate: float, out_path: str = "") -> List[Dict]:
    """Run the four stages in order; a stage that raises ends the run
    (a skipped stage would read as a measured one). ``fs`` is the
    headline cluster's client (configs #2/#4 reuse its warm worker);
    configs #3/#5 provision their own clusters."""
    rows = [
        config2_random_4k(jax, fs, device,
                          shard_bytes=min(shard_bytes, 64 << 20)),
        config3_prefetch(jax, device,
                         file_bytes=min(shard_bytes, 32 << 20)),
        config4_projection(jax, fs, device),
        config5_write_eviction(cold_write_rate=cold_write_rate),
    ]
    if out_path:
        with open(out_path, "w") as f:
            json.dump(rows, f, indent=1, sort_keys=True)
    return rows
