"""The token-window consumer against its plain reference, tiny, on the
CPU: results only. The whole-cell case goes through ``run_cell`` with
real role processes and 1 MiB shards, at the published widths (batches
of 60 windows of 1,025 tokens); its tiny configuration is written into
the test's own directory (``tests/data/tiny`` is the accepted cells')."""

import argparse
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.harness.discover import BENCH_DIR, load_module

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
BLOCK = 1 << 20
FILES = 12
SEED = 2**31 + 40


def _tiny(tmp_path) -> tuple:
    """``(spec, dir)`` of a one-cell benchmark: 12 x 1 MiB shards."""
    base = tmp_path / "bench"
    for sub in ("configs", "traffic"):
        (base / sub).mkdir(parents=True)
    with open(os.path.join(BENCH_DIR, "configs", "owt-tokens-32m.json")) as f:
        config = json.load(f)
    config.update(block_bytes=BLOCK, set_bytes=FILES * BLOCK,
                  writer_threads=2)
    (base / "configs" / "tiny-tokens.json").write_text(json.dumps(config))
    with open(os.path.join(BENCH_DIR, "traffic", "randwin-b60.json")) as f:
        traffic = json.load(f)
    traffic.update(files=FILES, warm_batches=4, write_runs=2,
                   cold_starts={"min": 3, "seconds": 0.1, "max": 3},
                   trace_seconds=0.5)
    (base / "traffic" / "randwin.json").write_text(json.dumps(traffic))
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"] = [{"name": "tiny.windows", "config": "tiny-tokens",
                          "traffic": "randwin", "chips": 1,
                          "why": "rehearsal"}]
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.windows"]
    return spec, str(base)


def test_cell_runs_tiny_and_is_correct(tmp_path, capsys):
    spec, base = _tiny(tmp_path)
    shm = tmp_path / "shm"
    shm.mkdir()
    args = argparse.Namespace(workload="tiny.windows", seed=SEED,
                              seconds=1.0, trace=0)
    result = run.run_cell(
        args, spec=spec, configs_dir=base, traffic_dir=base,
        peaks_path=os.path.join(TINY, "peaks.json"), platform="cpu",
        shm=str(shm))
    assert result["correct"] is True and result["failed"] == 0
    assert {"step_gbps", "first_batch_ms", "setup_s"} <= \
        set(result["metrics"])
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert os.listdir(shm) == []  # roles stopped, nothing left
    lines = {line.split(" ", 2)[1]: json.loads(line.split(" ", 2)[2])
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("[bench]")}
    notes = lines["check"]["notes"]
    assert notes["steps"] > 4  # the window made steps after the warm-up
    assert notes["windows_checked"] == 60 * notes["steps"]
    assert notes["batch_bytes_equal"] == [4, True]
    counters = lines["window"]["counters"]
    assert counters["Client.JaxWindowReads"] == \
        60 * counters["Client.JaxWindowBatches"]
    # 12 shards in a cache of 64: every window of the window is mapped
    assert counters["Client.JaxWindowMapped"] == \
        counters["Client.JaxWindowReads"]
    assert "Client.JaxWindowSplit" not in counters  # one block a shard


@pytest.fixture()
def consumer():
    """The consumer at tiny size, its device state built by ``open``
    without a cluster."""
    import jax

    mod = load_module("consumers", "token_windows")
    with open(os.path.join(BENCH_DIR, "configs", "owt-tokens-32m.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", "randwin-b60.json")) as f:
        traffic = json.load(f)
    config.update(block_bytes=64 << 10, set_bytes=5 * (64 << 10))
    traffic.update(files=5, warm_batches=1)
    c = mod.Consumer(config=config, traffic=traffic, seed=SEED,
                     devices=jax.devices()[:1], roles=None)
    c.new_loader = lambda fs: None
    c.open(None)
    return mod, c


def _as_bytes(c, files, starts) -> np.ndarray:
    return c.dataset.windows(files, starts, c.window_tokens) \
        .astype("<u2").view(np.uint8)


def _batches(c, n_steps: int) -> tuple:
    """The sampler's rows and the reference's own batches, as the loader
    must hand them over."""
    rows = [r for _s, r in zip(range(n_steps), c.sampler())]
    return rows, [_as_bytes(c, r[:, 0], r[:, 1] // 2).copy() for r in rows]


@pytest.mark.parametrize("spoil", [None, "other-shard", "other-offset",
                                   "flipped-byte", "kept-batch-byte"])
def test_check_holds_every_window_to_the_reference(consumer, spoil):
    import jax

    _mod, c = consumer
    rows, batches = _batches(c, 4)
    f, off = (int(x) for x in rows[2][3])  # window 3 of step 2
    if spoil == "other-shard":
        batches[2][3] = _as_bytes(c, [(f + 1) % c.dataset.n_files],
                                  [off // 2])[0]
    elif spoil == "other-offset":
        batches[2][3] = _as_bytes(c, [f], [off // 2 + 1])[0]
    elif spoil == "flipped-byte":
        batches[3][59, 100] ^= 1
    kept = spoil == "kept-batch-byte"
    for s, b in enumerate(batches):
        c.step(jax.device_put(b))
        if kept and s == c.warm_items:
            # the batch the check compares byte for byte, spoiled after
            # its step ran on the right bytes
            wrong = b.copy()
            wrong[0, 0] ^= 1
            c._kept = (s, jax.device_put(wrong))
    got = c.check()
    assert got["notes"]["steps"] == 4
    if spoil is None:
        assert got["failed"] == 0, got
        assert got["notes"]["batch_bytes_equal"] == [1, True]
    else:
        assert got["failed"] >= 1, got


def test_the_shard_shift_stays_in_the_vocabulary_and_moves_every_sum():
    mod = load_module("consumers", "token_windows")
    ds = mod.TokenSet(SEED, 3, 1 << 12)
    for i in (0, 1, 2, mod.VOCAB - 1, mod.VOCAB + 5, 70000):
        tok = ds.file(i).view("<u2")
        assert tok.max() < mod.VOCAB
        want = (ds._base.astype(np.int64) + i) % mod.VOCAB
        assert np.array_equal(tok, want)
    # the same offset in two shards never sums alike
    sums = {int(ds.windows([i], [7], 1025).astype(np.uint32).sum())
            for i in range(40)}
    assert len(sums) == 40
