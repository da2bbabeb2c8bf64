#!/usr/bin/env python
"""Headline benchmark: warm-cache sequential read GB/s per chip into HBM.

BASELINE.md config #1 (reference analogue: StressWorkerBench sequential
read, ``stress/shell/.../cli/worker/StressWorkerBench.java:47``) on the
TPU-native path: a LocalCluster (master + 1 worker, MEM tier on /dev/shm)
holds a warm dataset; the client's DeviceBlockLoader serves it as
device-resident ``jax.Array`` blocks.

Needs the chip: without a TPU it exits non-zero and prints no number
(a host rate under a device metric's name is worse than no row).

Phases:
  cold   : write-through into the worker cache
  raw    : RAW ``jax.device_put`` rate measured adjacently — the
           host->HBM ceiling the loader cannot exceed
  first  : p50 time-to-first-batch from a cold client (diagnostic)
  h2d    : warm host tier -> HBM via the loader (short-circuit mmap +
           device_put)
  hbm    : warm HBM tier consumed by a jitted reduction whose scale
           depends on the previous iteration (XLA cannot hoist the body;
           fetching the final scalar forces completion) — the headline
  e2e    : decode->train-step epoch: cached uint8 record blocks ->
           ``decode_image_records`` -> SGD step, the whole epoch inside
           ONE jit via ``lax.scan`` (step-in-scan: one dispatch per epoch)

Prints exactly ONE JSON line on stdout; diagnostics go to stderr.
vs_baseline = value / (0.9 * peak HBM GB/s of this ``device_kind``),
i.e. >= 1.0 meets the >=90%% of per-chip HBM bandwidth target from
BASELINE.json.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BLOCK_BYTES = int(os.environ.get("BENCH_BLOCK_BYTES", 32 << 20))
# 64 x 32 MiB = 2 GiB HBM working set: the round-2 data put the XLA
# while-loop's fixed per-iteration cost at ~57 us against a 0.66 ms
# read, an 8% tax; 4x the per-iteration read amortizes it to ~2%
NUM_BLOCKS = int(os.environ.get("BENCH_NUM_BLOCKS", 64))
EPOCHS = int(os.environ.get("BENCH_HBM_EPOCHS", 5))
# K scales inversely with the working set: K * NUM_BLOCKS * BLOCK_BYTES
# (total device-side bytes per epoch) matches round 2's 6.4 TB
K = int(os.environ.get("BENCH_CHAIN_ITERS", 3000))
UNROLL = int(os.environ.get("BENCH_UNROLL", 4))
#: peak HBM bandwidth of one chip in GB/s, keyed by the ``device_kind``
#: JAX reports. Source: Google Cloud documentation, "TPU v5e" system
#: architecture (16 GB HBM2e at 819 GB/s per chip). A kind that is not
#: here is an error, not a default.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}


def log(*args):
    print(*args, file=sys.stderr, flush=True)


def _kernel_candidate_names() -> list:
    """Single source of truth for reduce-kernel candidate names —
    BENCH_KERNEL validation (cheap, before the cluster boots) and
    factory construction both derive from this list."""
    from alluxio_tpu.ops import reduce_kernel

    names = [f"xla-u{u}" for u in sorted({4, 16, UNROLL})]
    names += [f"pallas-r{r}-u{UNROLL}"
              for r in reduce_kernel.CALIBRATION_ROWS]
    return names


def require_chip():
    """The one device this bench measures, and its peak HBM GB/s. No
    TPU, or a kind the peak table does not know: exit non-zero with the
    reason, no JSON line."""
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py needs a TPU: JAX found platform="
            f"{device.platform!r} ({device.device_kind}); no chip, no "
            f"number")
    peak = HBM_PEAK_GBPS.get(device.device_kind)
    if peak is None:
        raise SystemExit(
            f"no peak HBM bandwidth on record for device_kind="
            f"{device.device_kind!r}; add it to HBM_PEAK_GBPS with its "
            f"source")
    return device, peak


def main() -> None:
    device, peak_gbps = require_chip()

    import jax
    import jax.numpy as jnp

    from alluxio_tpu.client.jax_io import DeviceBlockLoader
    from alluxio_tpu.client.streams import WriteType
    from alluxio_tpu.minicluster import LocalCluster
    from alluxio_tpu.ops import reduce_kernel
    from alluxio_tpu.utils.compile_cache import ensure_compile_cache

    log(f"compile cache: {ensure_compile_cache()}")

    # fail a malformed BENCH_KERNEL HERE, before the cluster boots.
    # Validation is by FORMAT, not membership: a prior run's winner may
    # carry an unroll outside this run's BENCH_UNROLL set (e.g. xla-u8)
    # and is still buildable
    import re

    pinned = os.environ.get("BENCH_KERNEL", "")
    known = _kernel_candidate_names()
    if pinned and pinned not in known and not re.fullmatch(
            r"xla-u\d+|pallas-r\d+-u\d+", pinned):
        raise SystemExit(f"BENCH_KERNEL={pinned!r} unknown; "
                         f"candidates: {known}")

    log(f"device: platform={device.platform} kind={device.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__}")
    total_bytes = BLOCK_BYTES * NUM_BLOCKS

    base = tempfile.mkdtemp(prefix="atpu_bench_", dir="/dev/shm"
                            if os.path.isdir("/dev/shm") else None)
    try:
        with LocalCluster(base, num_workers=1, block_size=BLOCK_BYTES,
                          worker_mem_bytes=total_bytes + (256 << 20),
                          start_worker_heartbeats=True) as cluster:
            fs = cluster.file_system()
            rng = np.random.default_rng(0)
            # distinct content per shard
            payloads = [rng.integers(0, 255, size=BLOCK_BYTES,
                                     dtype=np.uint8).tobytes()
                        for _ in range(NUM_BLOCKS)]
            payload = payloads[0]
            t0 = time.monotonic()
            for i in range(NUM_BLOCKS):
                fs.write_all(f"/bench/shard-{i}", payloads[i],
                             write_type=WriteType.MUST_CACHE)
            cold_rate = total_bytes / (time.monotonic() - t0)
            log(f"cold write: {cold_rate / 1e9:.2f} GB/s")
            del payloads[1:]  # worker holds the data now; free host RAM

            # -- raw device_put ceiling ------------------------------------
            # distinct source arrays per put
            probe = np.frombuffer(payload, dtype=np.int32)
            prng = np.random.default_rng(99)

            def fresh_probe():
                return prng.integers(0, 1 << 30, size=BLOCK_BYTES // 4,
                                     dtype=np.int32)

            probes = [fresh_probe() for _ in range(4)]
            jax.device_put(probe, device).block_until_ready()  # warm path
            t0 = time.monotonic()
            raw_burst = jax.device_put(probes[0], device)
            raw_burst.block_until_ready()
            burst_gbps = BLOCK_BYTES / (time.monotonic() - t0) / 1e9
            t0 = time.monotonic()
            raws = [jax.device_put(p, device) for p in probes]
            jax.block_until_ready(raws)
            sustained_gbps = 4 * BLOCK_BYTES / (time.monotonic() - t0) / 1e9
            del raw_burst, raws, probes
            log(f"raw device_put ceiling: burst {burst_gbps:.2f} GB/s, "
                f"sustained {sustained_gbps:.2f} GB/s")

            paths = [f"/bench/shard-{i}" for i in range(NUM_BLOCKS)]
            loader = DeviceBlockLoader(fs, paths, device=device,
                                       hbm_bytes=total_bytes + (64 << 20),
                                       prefetch=2, dtype=np.int32)

            # p50 first-batch latency from warm host tier
            lat = []
            for s in range(min(4, NUM_BLOCKS)):  # 4.. stay untransferred
                l2 = DeviceBlockLoader(fs, paths[s:s + 1], device=device,
                                       hbm_bytes=0)
                t0 = time.monotonic()
                jax.block_until_ready(l2.load_block(0))
                lat.append(1000 * (time.monotonic() - t0))
                l2.close()
            raw_ms = 1000 * BLOCK_BYTES / (burst_gbps * 1e9)
            p50_ms = sorted(lat)[len(lat) // 2]
            p50_vs_floor = p50_ms / raw_ms if raw_ms > 0 else 0.0
            log(f"p50 first-batch: {p50_ms:.1f} ms "
                f"(raw {BLOCK_BYTES >> 20}MB device_put floor: {raw_ms:.1f} ms, "
                f"{p50_vs_floor:.2f}x)")

            # h2d ratio: interleave ADJACENT ceiling/loader pairs over a
            # subset and take the median ratio
            pair_ratios = []
            h2d = 0.0
            for _rep in range(3):
                # a shard subset this process has NOT transferred yet
                # (first-batch used 0-3; reps take 4-7, 8-11, 12-15).
                # sub_bytes follows len(sub): under a tiny
                # BENCH_NUM_BLOCKS the slice is short and counting a
                # fixed 4 blocks would overstate both rates
                lo_i = min(4 + 4 * _rep, max(0, NUM_BLOCKS - 4))
                sub = paths[lo_i:lo_i + 4]
                sub_bytes = len(sub) * BLOCK_BYTES
                ps = [fresh_probe() for _ in range(len(sub))]
                t0 = time.monotonic()
                raws = [jax.device_put(p, device) for p in ps]
                jax.block_until_ready(raws)
                ceil = sub_bytes / (time.monotonic() - t0) / 1e9
                del raws, ps
                l3 = DeviceBlockLoader(fs, sub, device=device,
                                       hbm_bytes=0, prefetch=2,
                                       dtype=np.int32)
                t0 = time.monotonic()
                bl = [b for b in l3.epoch()]
                jax.block_until_ready(bl)
                h2d = sub_bytes / (time.monotonic() - t0) / 1e9
                del bl
                l3.close()
                pair_ratios.append(h2d / max(ceil, 1e-9))
                log(f"  h2d pair: ceiling {ceil:.3f} GB/s, "
                    f"loader {h2d:.3f} GB/s, ratio {pair_ratios[-1]:.2f}")
            h2d_vs_ceiling = sorted(pair_ratios)[len(pair_ratios) // 2]
            log(f"h2d vs adjacent device_put ceiling: median "
                f"{h2d_vs_ceiling:.2f}x over {len(pair_ratios)} pairs")

            # warm the retained loader's HBM set (untimed)
            blocks = [b for b in loader.epoch()]
            jax.block_until_ready(blocks)

            # warm HBM epochs: a serialized on-device loop where every
            # iteration re-reads every cached block, scaled by a value that
            # depends on the previous iteration — XLA cannot hoist or cache
            # it, and fetching the final scalar forces real completion
            # (not just the enqueue).
            def make_consume(k, unroll):
                @jax.jit
                def consume(blocks, acc0):
                    # concatenating inside jit lets XLA fuse ONE reduce
                    # over all blocks (measured ~1.2% faster than 16
                    # separate reduces; the concat is fused, not
                    # materialized)
                    X = jnp.concatenate(blocks)

                    def body(i, acc):
                        return (jnp.sum(X * (acc % 3 + 1)) + acc) % 1000003

                    import jax.lax as lax

                    # unroll: several body copies per while-iteration —
                    # same k reads, 1/unroll of the loop-condition cost
                    return lax.fori_loop(0, k, body, acc0, unroll=unroll)

                return consume

            def make_consume_pallas(k, unroll, rows):
                @jax.jit
                def consume_pallas(blocks, acc0):
                    # explicit gridded HBM->VMEM pipeline (see
                    # ops/reduce_kernel.py); block height `rows` sets
                    # the DMA granularity — taller blocks amortize
                    # per-grid-step cost, calibration picks the winner
                    X = reduce_kernel.pad_to_kernel_shape(
                        jnp.concatenate(blocks).reshape(-1), rows=rows)

                    def body(i, acc):
                        return (reduce_kernel.scaled_sum(
                            X, acc % 3 + 1, rows=rows) + acc) % 1000003

                    return jax.lax.fori_loop(0, k, body, acc0,
                                             unroll=unroll)

                return consume_pallas

            # candidate factories built from the validated name list:
            # (name, fn(k) -> jitted consume). Unroll variants cut
            # while-loop condition overhead; pallas block-height
            # variants trade per-grid-step cost against DMA pipelining
            # depth. BENCH_UNROLL joins the unroll set via
            # _kernel_candidate_names so the env knob stays live.
            def mk_from_name(name):
                if name.startswith("xla-u"):
                    u = int(name[len("xla-u"):])
                    return lambda k: make_consume(k, u)
                r, u = name[len("pallas-r"):].split("-u")
                return lambda k: make_consume_pallas(k, int(u), int(r))

            # BENCH_KERNEL pins a candidate by name (e.g. a prior run's
            # calibration winner), skipping the calibration compiles
            if pinned:
                log(f"reduce kernel pinned via BENCH_KERNEL={pinned}")
            factories = [(n, mk_from_name(n))
                         for n in ([pinned] if pinned else known)]

            blocks = [b for b in loader.epoch()]  # HBM-resident now
            # calibrate at reduced K: ranking candidates costs k_cal/K
            # of a full epoch per sample, and per-call dispatch is a
            # common-mode offset that cannot reorder candidates.
            # Interleaved median-of-3 per candidate: one noisy sample
            # must not pick a slower kernel for the whole headline run.
            k_cal = min(K, max(100, K // 10))
            cal_fns = []
            if len(factories) == 1:
                # nothing to rank — skip the reduced-K compile entirely
                factories_to_rank = []
                cal = [(0.0, factories[0][0])]
            else:
                factories_to_rank = factories
            for name, mk in factories_to_rank:
                # a candidate the compiler refuses ends the run: it
                # leaves the candidate list, it is not skipped
                fn = mk(k_cal)
                int(fn(blocks, jnp.int32(1)))  # compile + warm
                cal_fns.append((name, fn))
            if factories_to_rank:
                samples = {name: [] for name, _ in cal_fns}
                for _rep in range(3):
                    for name, fn in cal_fns:
                        t0 = time.monotonic()
                        int(fn(blocks, jnp.int32(1)))
                        samples[name].append(time.monotonic() - t0)
                cal = sorted((sorted(ts)[1], name) for name, ts in
                             samples.items())
                # honesty guard: a candidate faster than physical HBM
                # bandwidth means the compiler hoisted/factored the
                # read out of the loop (e.g. sum(X*s) -> s*sum(X) with
                # loop-invariant sum(X)) — its timing no longer
                # measures reads; reject it.
                honest = []
                for t, n in cal:
                    rate = k_cal * total_bytes / max(t, 1e-9) / 1e9
                    if rate > 1.2 * peak_gbps:
                        log(f"calibration candidate {n} rejected: "
                            f"{rate:.0f} GB/s exceeds HBM peak — "
                            f"compiler hoisted the read")
                    else:
                        honest.append((t, n))
                # all rejected: the canonical xla-u4 (the headline-level
                # invalid marker below still flags the run if even that
                # one is hoisted)
                cal = (honest
                       or [tn for tn in cal if tn[1] == "xla-u4"]
                       or cal[-1:])
                # raw seconds, not GB/s: at reduced k_cal the dispatch
                # cost is a large common-mode offset, and a GB/s figure
                # here could be mistaken for headline evidence
                log(f"reduce kernel calibration (median of 3 at "
                    f"K={k_cal}): "
                    + ", ".join(f"{n}={t:.3f}s" for t, n in cal)
                    + f" -> using {cal[0][1]}")
                del samples
            del cal_fns
            consume = dict(factories)[cal[0][1]](K)
            _ = int(consume(blocks, jnp.int32(1)))  # compile + warm
            rates = []
            for e in range(EPOCHS):
                t0 = time.monotonic()
                blocks = [b for b in loader.epoch()]  # HBM hits: no host IO
                v = int(consume(blocks, jnp.int32(e)))  # fetch forces wait
                dt = time.monotonic() - t0
                rates.append(K * total_bytes / dt / 1e9)
            order = sorted(range(EPOCHS), key=lambda i: rates[i])
            value = rates[order[EPOCHS // 2]]
            hoist_suspect = value > 1.2 * peak_gbps
            if hoist_suspect:
                log(f"WARNING: headline {value:.0f} GB/s exceeds "
                    f"physical HBM bandwidth — the compiler likely "
                    f"hoisted the read; this run is marked invalid")
            log(f"warm HBM-tier read epochs GB/s: "
                f"{', '.join(f'{r:.1f}' for r in sorted(rates))} (K={K})")
            log(f"loader stats: {loader.hbm_stats()}")

            # -- e2e: decode -> train-step epoch over cached records -------
            _bench_e2e(jax, jnp, fs, device, rng)

            # -- BASELINE configs #2-#5 on the device (round-3 verdict #2:
            # every config measured on TPU with an explicit vs_baseline;
            # rows go to stderr as TPU-CONFIG lines + BENCH_TPU.json) ----
            if os.environ.get("BENCH_TPU_CONFIGS", "1") != "0":
                from alluxio_tpu.stress import tpu_suite

                # under chiprun_out/: what the chip tool brings back
                out_dir = os.path.join(
                    os.path.dirname(os.path.abspath(__file__)),
                    "chiprun_out")
                os.makedirs(out_dir, exist_ok=True)
                tpu_suite.run_all(
                    jax, fs, device, shard_bytes=BLOCK_BYTES,
                    cold_write_rate=cold_rate,
                    out_path=os.path.join(out_dir, "BENCH_TPU.json"))

            loader.close()
            fs.close()

        row = {
            "metric": "warm-cache sequential read GB/s/chip into HBM "
                      "(config #1, StressWorkerBench analogue)",
            "value": round(value, 2),
            "unit": "GB/s",
            "vs_baseline": round(value / (0.9 * peak_gbps), 3),
            "device": {"platform": device.platform,
                       "kind": device.device_kind,
                       "count": len(jax.devices())},
            # the loader judged against the adjacent raw device_put
            "h2d_vs_device_put_ceiling": round(h2d_vs_ceiling, 3),
            "p50_first_batch_vs_raw_floor": round(p50_vs_floor, 3),
        }
        if hoist_suspect:
            # machine-readable: a JSON consumer must never ingest a
            # rate the bench itself determined is physically impossible
            row["invalid"] = ("headline exceeds physical HBM "
                              "bandwidth — compiler hoisted the read")
            row["vs_baseline"] = 0.0
        print(json.dumps(row), flush=True)
    finally:
        shutil.rmtree(base, ignore_errors=True)


def _bench_e2e(jax, jnp, fs, device, rng) -> None:
    """ImageNet-style records -> decode -> SGD step, epoch-in-one-jit.

    The whole epoch runs as ONE jitted ``lax.scan`` over batches — the
    idiomatic TPU input-pipeline shape (step-in-scan).
    """
    import optax

    from alluxio_tpu.client.jax_io import DeviceBlockLoader
    from alluxio_tpu.client.streams import WriteType
    from alluxio_tpu.ops.decode import (
        decode_image_records, encode_image_records, image_record_bytes,
    )

    H = W = 64
    C = 3
    rec_bytes = image_record_bytes(H, W, C)       # 4 + 12288
    n_blocks = int(os.environ.get("BENCH_E2E_BLOCKS", 4))
    recs_per_block = BLOCK_BYTES // rec_bytes
    batch = 128
    n_batches = (n_blocks * recs_per_block) // batch

    for i in range(n_blocks):
        imgs = rng.integers(0, 255, size=(recs_per_block, H, W, C),
                            dtype=np.uint8)
        labels = rng.integers(0, 1000, size=recs_per_block, dtype=np.int32)
        raw = encode_image_records(imgs, labels)
        raw += b"\0" * (BLOCK_BYTES - len(raw))   # pad to block size
        fs.write_all(f"/bench/e2e-{i}", raw, write_type=WriteType.MUST_CACHE)

    paths = [f"/bench/e2e-{i}" for i in range(n_blocks)]
    loader = DeviceBlockLoader(fs, paths, device=device,
                               hbm_bytes=n_blocks * BLOCK_BYTES + (8 << 20))

    n_classes, feat = 1000, H * W * C
    params = {
        "w": jax.device_put(
            (rng.standard_normal((feat, n_classes)) * 0.01
             ).astype(np.float32), device),
        "b": jax.device_put(np.zeros(n_classes, np.float32), device),
    }
    tx = optax.sgd(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def train_epoch(params, opt_state, blocks):
        """blocks: (n_blocks, BLOCK_BYTES) uint8. One scan step = one
        decoded batch through loss+grad+update."""
        usable = recs_per_block * rec_bytes
        recs = blocks[:, :usable].reshape(-1, rec_bytes)
        recs = recs[:n_batches * batch].reshape(n_batches, batch, rec_bytes)

        def loss_fn(p, imgs, labels):
            x = imgs.reshape(imgs.shape[0], -1).astype(jnp.float32)
            logits = x @ p["w"] + p["b"]
            onehot = jax.nn.one_hot(labels, n_classes)
            return -jnp.mean(jnp.sum(
                jax.nn.log_softmax(logits) * onehot, axis=-1))

        def step(carry, rec_batch):
            p, o = carry
            imgs, labels = decode_image_records(
                rec_batch, height=H, width=W, channels=C)
            loss, grads = jax.value_and_grad(loss_fn)(p, imgs, labels)
            updates, o = tx.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            return (p, o), loss

        (params, opt_state), losses = jax.lax.scan(
            step, (params, opt_state), recs)
        return params, opt_state, losses.mean()

    blocks = jnp.stack([b for b in loader.epoch()])   # warm into HBM
    params, opt_state, l0 = train_epoch(params, opt_state, blocks)
    _ = float(l0)  # compile + warm
    rates = []
    for _e in range(3):
        t0 = time.monotonic()
        blocks = jnp.stack([b for b in loader.epoch()])
        params, opt_state, loss = train_epoch(params, opt_state, blocks)
        loss = float(loss)  # forces the whole epoch
        dt = time.monotonic() - t0
        rates.append(n_batches * batch * rec_bytes / dt / 1e9)
    log(f"e2e decode+train epochs (warm, {n_batches} batches x {batch} "
        f"recs, one scan-jit per epoch): "
        f"{', '.join(f'{r:.2f}' for r in sorted(rates))} GB/s into the "
        f"step, final loss {loss:.3f}")

    # -- flagship model: cached records -> patchify -> ViT train epoch --
    # (round-2 verdict weak #4: the e2e must exercise the actual
    # transformer in models/, not a stand-in linear softmax)
    from alluxio_tpu.models.transformer import (
        TransformerConfig, images_to_tokens, init_params,
    )
    from alluxio_tpu.models.transformer import loss_fn as vit_loss

    patch = 16
    cfg = TransformerConfig(
        vocab_or_patch_dim=patch * patch * C, d_model=256, n_heads=8,
        d_ff=1024, n_layers=4, n_classes=n_classes,
        max_len=(H // patch) * (W // patch))
    vit_params = jax.device_put(
        init_params(cfg, jax.random.PRNGKey(0)), device)
    vit_tx = optax.adamw(3e-4)
    vit_opt = vit_tx.init(vit_params)
    vit_batch = 64
    vit_batches = (n_blocks * recs_per_block) // vit_batch

    @jax.jit
    def vit_epoch(p, o, blocks):
        usable = recs_per_block * rec_bytes
        recs = blocks[:, :usable].reshape(-1, rec_bytes)
        recs = recs[:vit_batches * vit_batch].reshape(
            vit_batches, vit_batch, rec_bytes)

        def step(carry, rec_batch):
            p, o = carry
            imgs, labels = decode_image_records(
                rec_batch, height=H, width=W, channels=C)
            tokens = images_to_tokens(imgs, patch=patch)
            loss, grads = jax.value_and_grad(vit_loss)(
                p, tokens, labels, cfg)
            updates, o = vit_tx.update(grads, o, p)
            p = optax.apply_updates(p, updates)
            return (p, o), loss

        (p, o), losses = jax.lax.scan(step, (p, o), recs)
        return p, o, losses.mean()

    blocks = jnp.stack([b for b in loader.epoch()])
    vit_params, vit_opt, l0 = vit_epoch(vit_params, vit_opt, blocks)
    _ = float(l0)  # compile + warm
    vit_rates, vit_losses = [], []
    for _e in range(3):
        t0 = time.monotonic()
        blocks = jnp.stack([b for b in loader.epoch()])
        vit_params, vit_opt, vloss = vit_epoch(vit_params, vit_opt,
                                               blocks)
        vloss = float(vloss)
        dt = time.monotonic() - t0
        vit_rates.append(vit_batches * vit_batch * rec_bytes / dt / 1e9)
        vit_losses.append(vloss)
    log(f"e2e flagship ViT train epochs ({cfg.n_layers}L/"
        f"{cfg.d_model}d bf16, {vit_batches} batches x {vit_batch}): "
        f"{', '.join(f'{r:.2f}' for r in sorted(vit_rates))} GB/s into "
        f"the step, loss {vit_losses[0]:.3f} -> {vit_losses[-1]:.3f}")
    loader.close()


def suite() -> None:
    """``bench.py --suite``: run the whole BASELINE config family
    (stress suite) and persist the per-config JSON lines to
    BENCH_SUITE.json; stdout gets ONE summary line."""
    from alluxio_tpu.stress.__main__ import run_suite

    results = run_suite()
    out = [json.loads(r.json_line()) for r in results]
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BENCH_SUITE.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    from alluxio_tpu.stress.__main__ import HOST_CALIBRATION_BENCH

    # the host-calibration stamp is not a bench: it can never fail and
    # must not inflate the pass ratio
    real = [r for r in results if r.bench != HOST_CALIBRATION_BENCH]
    ok = sum(1 for r in real if r.errors == 0)
    print(json.dumps({
        "metric": "stress-suite configs passing (BASELINE #1-#5 + "
                  "master op/s)",
        "value": ok,
        "unit": f"of {len(real)} benches",
        "vs_baseline": round(ok / len(real), 3) if real else 0.0,
    }), flush=True)


if __name__ == "__main__":
    if "--suite" in sys.argv:
        suite()
    else:
        main()
