#!/usr/bin/env python3
"""The served read path, as deployed, on the chip — the quickest proof
that the system still starts there.

    python3 chip_smoke.py [--seed N] [--blocks 256] [--block-mib 32]

One client process (this one) owns the chip. Master and worker are
separate OS processes started through the role entry point
(``python -m alluxio_tpu.shell.main master|worker``, what
``bin/alluxio-tpu-start.sh`` execs) with their MEM tier on /dev/shm;
they are started FIRST and must never open the accelerator. Then:

  write     256 shards x 32 MiB = 8 GiB made from --seed, MUST_CACHE
  resident  DeviceBlockLoader(hbm_bytes = set + slack): epoch 1 takes
            every block worker-SHM -> device_put -> HBM, epoch 2 is all
            HBM hits with no host bytes read; every block summed on the
            device against the host data
  pallas    ops/reduce_kernel.scaled_sum compiled by Mosaic over a
            loaded block, every calibration height, equal to XLA
  evict     the same set through a loader holding a quarter of it:
            the HBM store never exceeds its capacity, bytes still right
  consumer  record shards -> batched_device_iterator ->
            decode_image_records -> jitted train steps, loss finite
  mesh      MeshBlockCache over EVERY device found (a quarter of the
            set per device): placement owners, report to the master,
            global_batch / ring_shift / replicate equal to host bytes,
            batch assembly free of all-gather

No TPU, no result: exits non-zero unless ``jax.devices()[0].platform``
is ``"tpu"``. Any leg that raises ends the run non-zero. The last line
of stdout is ``{"ok": true, "device": {...}}``. It prints counts, sizes
and its own wall-clock seconds, never a rate: that is the benchmark's
job. The legs are plain functions taking sizes, so a CPU test calls
them tiny (``tests/test_chip_smoke.py``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

MIB = 1 << 20
#: H x W x C of the consumer leg's image records (bench.py's e2e shape)
IMAGE_SHAPE = (64, 64, 3)
CONSUMER_BLOCKS = 4


def say(tag: str, **facts) -> None:
    print(f"[smoke] {tag} " + json.dumps(facts, sort_keys=True), flush=True)


# ------------------------------------------------------------------ data

class Dataset:
    """``n_blocks`` shards of ``block_bytes`` made from one seed: a
    random base block, shard ``i`` = base + i (mod 256) with its index
    stamped in the first 8 bytes. Any shard is rebuilt on the host in
    one pass, so checks never hold the set in host memory."""

    def __init__(self, seed: int, n_blocks: int, block_bytes: int) -> None:
        self.n_blocks = n_blocks
        self.block_bytes = block_bytes
        self._base = np.random.default_rng(seed).integers(
            0, 256, size=block_bytes, dtype=np.uint8)
        self.paths = [f"/smoke/shard-{i:04d}" for i in range(n_blocks)]
        #: host-side byte sum of every shard mod 2**32 (the reference
        #: the device's uint32 sums are held to), filled by ``write``
        self.sums = [0] * n_blocks

    @property
    def total_bytes(self) -> int:
        return self.n_blocks * self.block_bytes

    def block(self, i: int, out=None) -> np.ndarray:
        out = np.add(self._base, np.uint8(i % 256), out=out)
        out[:8] = np.frombuffer(np.uint64(i).tobytes(), dtype=np.uint8)
        return out

    def write(self, fs, io_threads: int = 4) -> None:
        """``fs.write_all(..., MUST_CACHE)`` per shard, a few at once."""
        from alluxio_tpu.client.streams import WriteType

        tls = threading.local()

        def put(i: int) -> None:
            if not hasattr(tls, "buf"):  # one buffer a thread, reused
                tls.buf = np.empty(self.block_bytes, np.uint8)
            data = self.block(i, out=tls.buf)
            self.sums[i] = int(data.sum(dtype=np.uint64)) & 0xFFFFFFFF
            fs.write_all(self.paths[i], data,
                         write_type=WriteType.MUST_CACHE)

        with ThreadPoolExecutor(io_threads) as pool:
            list(pool.map(put, range(self.n_blocks)))


def fit_to_shm(shm_dir: str, n_blocks: int, block_bytes: int) -> int:
    """The shard count /dev/shm can hold beside the consumer leg's
    shards and 512 MiB of slack; anything less than asked is a cut the
    caller prints."""
    free = shutil.disk_usage(shm_dir).free
    room = free - CONSUMER_BLOCKS * block_bytes - 512 * MIB
    return max(0, min(n_blocks, room // block_bytes))


def mem_tier_bytes(held: int) -> int:
    """A MEM tier that keeps ``held`` bytes resident: the worker frees
    a tier that passes its 0.95 high watermark down to 0.7, demoting
    blocks to SSD, so the set sits at 0.9 of the tier."""
    return int(held / 0.9) + MIB


# ----------------------------------------------------------------- roles

def start_roles(base: str, *, mem_bytes: int, block_bytes: int):
    """Master + worker as real role processes, configured as a site
    would be: MEM tier sized for the set, and the block size files get
    (the MASTER's ``atpu.user.block.size.bytes.default`` decides it — a
    client-side value is never consulted — and a short-circuit write
    reserves one whole block of the tier up front). Returns the running
    cluster (caller stops it)."""
    from alluxio_tpu.minicluster.multi_process import MultiProcessCluster

    return MultiProcessCluster(
        base, num_masters=1, num_workers=1,
        extra_conf={
            "atpu.worker.ramdisk.size": str(mem_bytes),
            "atpu.user.block.size.bytes.default": str(block_bytes),
        }).start()


def assert_roles_off_chip(cluster) -> list:
    """No role process may have opened the accelerator: a live role has
    neither libtpu mapped nor an accelerator device node open."""
    seen = []
    for p in cluster.masters + cluster.workers:
        if not p.alive:
            raise RuntimeError(f"{p.role} process died; log: {p.log_path}")
        pid = p.proc.pid
        with open(f"/proc/{pid}/maps") as f:
            libtpu = "libtpu" in f.read()
        fds = []
        for fd in os.listdir(f"/proc/{pid}/fd"):
            with contextlib.suppress(OSError):
                fds.append(os.readlink(f"/proc/{pid}/fd/{fd}"))
        nodes = [t for t in fds if t.startswith(("/dev/accel", "/dev/vfio"))]
        if libtpu or nodes:
            raise RuntimeError(
                f"{p.role} (pid {pid}) touched the accelerator: "
                f"libtpu mapped={libtpu}, device nodes={nodes}")
        seen.append(f"{p.role}:{pid}")
    return seen


def client_counters() -> dict:
    """What the loader served and by which route: counts of blocks and
    bytes (its ``...Us`` counters add up time, which no leg holds to a
    value)."""
    from alluxio_tpu.metrics import metrics

    return {k: v for k, v in metrics().snapshot().items()
            if k.startswith(("Client.Jax", "Client.BytesRead."))
            and not k.endswith("Us")}


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def _check_sums(loader, sums) -> None:
    """Every block of one epoch summed ON the device, one at a time,
    against the host data (the sums come back in one fetch)."""
    import jax

    from alluxio_tpu.ops.decode import sum_bytes

    got = [int(v) for v in
           jax.device_get([sum_bytes(b) for b in loader.epoch()])]
    bad = [i for i, (g, w) in enumerate(zip(got, sums)) if g != w]
    if len(got) != len(sums) or bad:
        raise AssertionError(
            f"device byte sums differ from host data: {len(got)} blocks "
            f"for {len(sums)}, first mismatches {bad[:8]}")


# ------------------------------------------------------------------ legs

def leg_resident(fs, data: Dataset, device, *,
                 slack: int = 64 * MIB) -> None:
    """Epoch 1: SHM -> device_put -> HBM. Epoch 2: all HBM hits, no
    host bytes."""
    from alluxio_tpu.client.jax_io import DeviceBlockLoader

    n = data.n_blocks
    with contextlib.closing(DeviceBlockLoader(
            fs, data.paths, device=device,
            hbm_bytes=data.total_bytes + slack)) as loader:
        c0 = client_counters()
        _check_sums(loader, data.sums)
        e1 = _delta(client_counters(), c0)
        # how many of them the kernel mapped whole is the host's answer
        # (0 where it has neither the advice nor mlock), and how many
        # blocks left before the queue held their successor is the
        # pacing's: shown, not held
        populated = e1.pop("Client.JaxPrefaultPopulated", 0)
        early = [e1.pop("Client.JaxLoaderEarlyYields", 0)]
        if e1 != {"Client.JaxShortCircuitBlocks": n,
                  "Client.JaxHbmAdopts": n,
                  "Client.JaxPrefaultBlocks": n,
                  "Client.JaxLoaderYields": n,
                  "Client.BytesRead.shm": data.total_bytes}:
            raise AssertionError(f"epoch 1 was not all SHM->HBM: {e1}")
        c1 = client_counters()
        _check_sums(loader, data.sums)
        e2 = _delta(client_counters(), c1)
        early.append(e2.pop("Client.JaxLoaderEarlyYields", 0))
        if e2 != {"Client.JaxHbmHits": n, "Client.JaxLoaderYields": n}:
            raise AssertionError(f"epoch 2 was not all HBM hits: {e2}")
        stats = loader.hbm_stats()
    if stats != {"hbm_bytes": data.total_bytes, "hbm_pages": n}:
        raise AssertionError(f"HBM tier does not hold the set: {stats}")
    say("resident", epoch1=e1, populated=populated, epoch2=e2,
        early_yields=early, **stats)


def leg_pallas(fs, data: Dataset, device, *,
               interpret: bool = False) -> None:
    """``scaled_sum`` at every calibration height over one block loaded
    as int32, run the way bench.py runs it — inside a ``fori_loop``
    whose scale depends on the carry (Mosaic budgets VMEM differently
    there than in a straight-line jit) — equal to the XLA reduce and to
    the host's, int32 wrap-around on all three sides."""
    import jax
    import jax.numpy as jnp

    from alluxio_tpu.client.jax_io import DeviceBlockLoader
    from alluxio_tpu.ops import reduce_kernel

    with contextlib.closing(DeviceBlockLoader(
            fs, data.paths[:1], device=device, dtype=np.int32)) as loader:
        x = loader.load_block(0)

    def chained(reduce_fn, k=3):
        def body(_i, acc):
            return (reduce_fn(acc % 3 + 1) + acc) % 1000003

        return jax.lax.fori_loop(0, k, body, jnp.int32(1))

    want = int(jax.jit(lambda a: chained(lambda s: jnp.sum(a * s)))(x))
    host, acc = data.block(0).view(np.int32).astype(np.int64), 1
    for _ in range(3):
        total = int((host * (acc % 3 + 1)).sum())
        total = (total + 2**31) % 2**32 - 2**31  # the int32 the chip holds
        acc = (total + acc) % 1000003
    if want != acc:
        raise AssertionError(f"XLA reduce {want} != host {acc}")
    for rows in reduce_kernel.CALIBRATION_ROWS:
        fn = jax.jit(lambda a, rows=rows: chained(
            lambda s: reduce_kernel.scaled_sum(
                reduce_kernel.pad_to_kernel_shape(a, rows=rows), s,
                rows=rows, interpret=interpret)))
        got = int(fn(x))
        if got != want:
            raise AssertionError(
                f"pallas scaled_sum rows={rows}: {got} != XLA {want}")
    say("pallas", interpret=interpret, equal_to_xla=True,
        rows=list(reduce_kernel.CALIBRATION_ROWS), elems=int(x.size))


def leg_evict(fs, data: Dataset, device, *, capacity: int) -> None:
    """The set through an HBM tier a fraction of its size: the store
    evicts, never exceeds its capacity, and the bytes stay right."""
    from alluxio_tpu.client.jax_io import DeviceBlockLoader
    from alluxio_tpu.ops.decode import sum_bytes

    n = data.n_blocks
    high = 0
    with contextlib.closing(DeviceBlockLoader(
            fs, data.paths, device=device, hbm_bytes=capacity)) as loader:
        for epoch in range(2):
            for i, b in enumerate(loader.epoch()):
                if int(sum_bytes(b)) != data.sums[i]:
                    raise AssertionError(
                        f"evict epoch {epoch} block {i}: bytes differ")
                high = max(high, loader.hbm_stats()["hbm_bytes"])
            if epoch == 0:
                # the tail of the scan is resident, the head was evicted
                c0 = client_counters()
                tail = int(sum_bytes(loader.load_block(n - 1)))
                head = int(sum_bytes(loader.load_block(0)))
                d = _delta(client_counters(), c0)
                if (tail, head) != (data.sums[n - 1], data.sums[0]) or \
                        d.get("Client.JaxHbmHits") != 1 or \
                        d.get("Client.JaxShortCircuitBlocks") != 1:
                    raise AssertionError(
                        f"evict: expected one hit and one re-read: {d}")
                high = max(high, loader.hbm_stats()["hbm_bytes"])
        stats = loader.hbm_stats()
    if high > capacity or \
            stats["hbm_pages"] != min(capacity // data.block_bytes, n):
        raise AssertionError(f"HBM store over capacity {capacity}: high "
                             f"water {high}, {stats}")
    say("evict", capacity=capacity, high_water=high, **stats)


def leg_consumer(fs, device, *, seed: int, block_bytes: int,
                 n_blocks: int = CONSUMER_BLOCKS, batch: int = 128,
                 image_shape=IMAGE_SHAPE) -> None:
    """The flagship consumer, as ``examples/jax_training_pipeline.py``:
    record shards -> batches on the device -> decode -> train steps."""
    import jax
    import jax.numpy as jnp
    import optax

    from alluxio_tpu.client.jax_io import (
        DeviceBlockLoader, batched_device_iterator,
    )
    from alluxio_tpu.client.streams import WriteType
    from alluxio_tpu.ops.decode import (
        decode_image_records, encode_image_records, image_record_bytes,
    )

    h, w, c = image_shape
    rec_bytes = image_record_bytes(h, w, c)
    per_block = block_bytes // rec_bytes
    n_classes = 1000
    rng = np.random.default_rng(seed + 1)
    paths, labels = [], []
    for i in range(n_blocks):
        imgs = rng.integers(0, 256, size=(per_block, h, w, c),
                            dtype=np.uint8)
        lab = rng.integers(0, n_classes, size=per_block, dtype=np.int32)
        raw = encode_image_records(imgs, lab)
        raw += b"\0" * (block_bytes - len(raw))  # records never straddle
        paths.append(f"/smoke/records-{i}")
        fs.write_all(paths[-1], raw, write_type=WriteType.MUST_CACHE)
        labels.append(lab)
    labels = np.concatenate(labels)

    params = {"w": jnp.zeros((h * w * c, n_classes), jnp.float32),
              "b": jnp.zeros((n_classes,), jnp.float32)}
    tx = optax.sgd(1e-3)
    opt = tx.init(params)

    @jax.jit
    def train_step(params, opt, rec_batch):
        imgs, lab = decode_image_records(rec_batch, height=h, width=w,
                                         channels=c)

        def loss_fn(p):
            x = imgs.reshape(imgs.shape[0], -1).astype(jnp.float32)
            logits = x @ p["w"] + p["b"]
            return -jnp.mean(jax.nn.log_softmax(logits)[
                jnp.arange(lab.shape[0]), lab])

        loss, grads = jax.value_and_grad(loss_fn)(params)
        upd, opt = tx.update(grads, opt, params)
        return optax.apply_updates(params, upd), opt, loss, lab

    losses, seen = [], []
    with contextlib.closing(DeviceBlockLoader(
            fs, paths, device=device,
            hbm_bytes=n_blocks * block_bytes + 8 * MIB)) as loader:
        for rec_batch in batched_device_iterator(
                loader, record_bytes=rec_bytes, batch_size=batch):
            params, opt, loss, lab = train_step(params, opt, rec_batch)
            losses.append(loss)
            seen.append(lab)
        losses = np.asarray(jax.device_get(losses))
        seen = np.concatenate(jax.device_get(seen))
    steps = (n_blocks * per_block) // batch
    if len(losses) != steps or not np.isfinite(losses).all():
        raise AssertionError(
            f"consumer: {len(losses)} steps for {steps}, losses "
            f"{losses[:4]}..{losses[-2:]}")
    if not np.array_equal(seen, labels[:steps * batch]):
        raise AssertionError("consumer: decoded labels differ from the "
                             "records written")
    say("consumer", steps=steps, batch=batch, record_bytes=rec_bytes,
        first_loss=float(losses[0]), last_loss=float(losses[-1]))


def leg_mesh(fs, data: Dataset, devices, *, blocks_per_device: int,
             batch: int = 8) -> None:
    """The ICI data plane over ``devices``: load a sharded warm set,
    then the three collective reads, each held to the host bytes."""
    import jax
    import jax.numpy as jnp

    from alluxio_tpu.parallel.ici_store import MeshBlockCache
    from alluxio_tpu.parallel.mesh import make_mesh

    n_dev = len(devices)
    n = min(data.n_blocks, blocks_per_device * n_dev)
    per_dev = -(-n // n_dev)
    cache = MeshBlockCache(make_mesh(devices=devices),
                           block_bytes=data.block_bytes,
                           client_host=f"chip-smoke-{os.getpid()}")
    cached = cache.load_global(fs, data.paths[:n])

    owners = cache.describe_placement(cached)
    if sorted(owners) != list(range(n_dev)) or \
            any(len(v) != per_dev for v in owners.values()):
        raise AssertionError(f"placement owners: {owners}")
    # what the master recorded: one block of every owner, read back
    for pos in range(n_dev):
        bid = cache.block_ids[pos * per_dev]
        info = fs.store.block_master.get_block_info(bid)
        where = [(loc.address.host, loc.address.tiered_identity.tiers[-1]
                  .value) for loc in info.device_locations]
        if where != [(cache.client_host, str(pos))]:
            raise AssertionError(
                f"master placement of block {bid} (mesh {pos}): {where}")

    @jax.jit
    def _row_sums(x):
        return jnp.sum(x.astype(jnp.uint32), axis=1)

    def row_sums(x):  # one fetch, not one per row
        return np.asarray(_row_sums(x)).tolist()

    def expect_sums(order):
        return [data.sums[g] if g < n else 0 for g in order]

    total = per_dev * n_dev
    if row_sums(cached) != expect_sums(range(total)):
        raise AssertionError("mesh: loaded shards differ from host data")

    # global_batch: rows from every owner, assembled over ICI
    idx = [(k * (n - 1)) // (batch - 1) for k in range(batch)] \
        if n > 1 else [0] * batch
    got = np.asarray(cache.global_batch(cached, np.asarray(idx)))
    scratch = np.empty(data.block_bytes, np.uint8)
    for k, g in enumerate(idx):
        if not np.array_equal(got[k], data.block(g, out=scratch)):
            raise AssertionError(f"global_batch row {k} (block {g}) "
                                 f"differs from the host bytes")
    del got
    fn = cache.batch_fn(per_dev)
    hlo = fn.lower(cached, jnp.asarray(idx)).compile().as_text()
    if "all-gather" in hlo or (n_dev > 1 and "all-reduce" not in hlo):
        raise AssertionError("batch assembly must reduce a batch, not "
                             "gather the warm set")

    # ring_shift: device d now holds device d+1's shard
    shifted = cache.ring_shift(cached, 1)
    order = [(g + per_dev) % total for g in range(total)]
    if row_sums(shifted) != expect_sums(order):
        raise AssertionError("ring_shift: shards are not the "
                             "neighbours' shards")
    for pos in range(n_dev):  # one row per device, byte for byte
        g = order[pos * per_dev]
        if g < n and not np.array_equal(
                np.asarray(shifted[pos * per_dev]),
                data.block(g, out=scratch)):
            raise AssertionError(f"ring_shift row on mesh {pos} differs "
                                 f"from the host bytes")
    del shifted

    # replicate: every device holds the hot block
    hot = n - 1
    rep = cache.replicate(cached, hot)
    want = data.block(hot, out=scratch)
    copies = [np.array_equal(np.asarray(s.data), want)
              for s in rep.addressable_shards]
    if len(copies) != n_dev or not all(copies):
        raise AssertionError(f"replicate: copies equal to host {copies}")
    cache.drop_placement(fs)
    say("mesh", devices=n_dev, blocks=n, per_device=per_dev,
        owners=sorted(owners), batch=idx, all_gather_free=True,
        bytes_per_device=per_dev * data.block_bytes)


# ------------------------------------------------------------------ main

class CompileLog:
    """Counts compiles and persistent-cache hits through JAX's own
    monitoring events."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.requests = self.hits = 0
        self.seconds = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--blocks", type=int, default=256)
    ap.add_argument("--block-mib", type=int, default=32)
    args = ap.parse_args(argv)
    block_bytes = args.block_mib * MIB
    t_start = time.monotonic()

    import jax  # importing is not initialising: the chip is still free

    if "tpu" not in (jax.config.jax_platforms or "tpu"):
        raise SystemExit(
            f"chip_smoke.py needs a TPU: JAX is pinned to "
            f"{jax.config.jax_platforms!r}; no chip, no result")

    shm = "/dev/shm"
    n_blocks = fit_to_shm(shm, args.blocks, block_bytes)
    if n_blocks < 4:
        raise SystemExit(f"{shm} cannot hold 4 shards of "
                         f"{args.block_mib} MiB")
    if n_blocks < args.blocks:
        say("CUT", asked_blocks=args.blocks, blocks=n_blocks,
            reason=f"{shm} free space")
    data = Dataset(args.seed, n_blocks, block_bytes)

    base = tempfile.mkdtemp(prefix="atpu_smoke_", dir=shm)
    cluster = None
    try:
        # roles FIRST: a role that took the chip would starve the client
        cluster = start_roles(
            base, block_bytes=block_bytes, mem_bytes=mem_tier_bytes(
                data.total_bytes + CONSUMER_BLOCKS * block_bytes))

        from alluxio_tpu import native
        from alluxio_tpu.utils.compile_cache import ensure_compile_cache

        devices = jax.devices()
        device = devices[0]
        if device.platform != "tpu":
            raise SystemExit(
                f"chip_smoke.py needs a TPU: JAX found platform="
                f"{device.platform!r} ({device.device_kind}); no chip, "
                f"no result")
        cache_dir = ensure_compile_cache()
        compiles = CompileLog()
        nat = native.status()
        if nat["toolchain"] and nat["rung"] != "native":
            raise RuntimeError(f"native build failed: {nat['error']}")
        say("start", platform=device.platform, device_kind=device.device_kind,
            device_count=len(devices), jax=jax.__version__,
            native_rung=nat["rung"], native_toolchain=nat["toolchain"],
            compile_cache=cache_dir, seed=args.seed, blocks=n_blocks,
            block_bytes=block_bytes, set_bytes=data.total_bytes,
            roles=assert_roles_off_chip(cluster))

        fs = cluster.file_system()
        marks = {}

        def timed(name, fn, *a, **kw):
            t0 = time.monotonic()
            out = fn(*a, **kw)
            marks[name] = round(time.monotonic() - t0, 3)
            return out

        timed("write", data.write, fs)
        timed("resident", leg_resident, fs, data, device)
        timed("pallas", leg_pallas, fs, data, device)
        timed("evict", leg_evict, fs, data, device,
              capacity=data.total_bytes // 4)
        timed("consumer", leg_consumer, fs, device, seed=args.seed,
              block_bytes=block_bytes)
        timed("mesh", leg_mesh, fs, data, devices,
              blocks_per_device=max(1, n_blocks // 4))
        fs.close()
        roles = assert_roles_off_chip(cluster)

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
        say("done", roles_off_chip=roles, peak_bytes_in_use=peak,
            leg_wall_s=marks,
            total_wall_s=round(time.monotonic() - t_start, 3),
            compile_requests=compiles.requests,
            compile_cache_hits=compiles.hits,
            compile_wall_s=round(compiles.seconds, 3))
    finally:
        if cluster is not None:
            cluster.stop()
        shutil.rmtree(base, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
