"""The shuffled-epoch consumer against its plain reference, tiny, on the
CPU: results only. The whole-cell case goes through ``run_cell`` with
real role processes, 1 MiB blocks and the prefetch service's heartbeat
thread running; its tiny configuration is written into the test's own
directory (``tests/data/tiny`` is the accepted cells')."""

import argparse
import json
import os

import numpy as np

from benchmark import run
from benchmark.harness import data as bdata
from benchmark.harness.discover import BENCH_DIR, load_module

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
BLOCK = 1 << 20
FILES = 12


def _tiny(tmp_path) -> tuple:
    """``(spec, dir)`` of a one-cell benchmark: 12 x 1 MiB through a tier
    of 8 blocks, 4 blocks of prefetch budget."""
    base = tmp_path / "bench"
    for sub in ("configs", "traffic"):
        (base / sub).mkdir(parents=True)
    with open(os.path.join(BENCH_DIR, "configs", "shuffled-32m.json")) as f:
        config = json.load(f)
    config.update(block_bytes=BLOCK, set_bytes=FILES * BLOCK,
                  hbm_bytes=8 * BLOCK, writer_threads=2)
    config["service"].update({"atpu.prefetch.budget.bytes": "4MB",
                              "atpu.prefetch.lookahead.blocks": 8})
    (base / "configs" / "tiny-shuffled.json").write_text(json.dumps(config))
    (base / "traffic" / "epochs.json").write_text(json.dumps({
        "files": FILES, "warm_files": FILES, "inflight": 2, "write_runs": 2,
        "cold_starts": {"min": 3, "seconds": 0.1, "max": 3},
        "trace_seconds": 0.5}))
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"] = [{"name": "tiny.shuffled", "config": "tiny-shuffled",
                          "traffic": "epochs", "chips": 1,
                          "why": "rehearsal"}]
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.shuffled"]
    return spec, str(base)


def test_cell_runs_tiny_and_is_correct(tmp_path, capsys):
    spec, base = _tiny(tmp_path)
    shm = tmp_path / "shm"
    shm.mkdir()
    args = argparse.Namespace(workload="tiny.shuffled", seed=2**31 + 35,
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
    assert lines["setup"]["manifest_ms"] > 0
    notes = lines["check"]["notes"]
    window = notes["prefetch_window"]
    consumed = sum(window[f"Client.Prefetch{k}"]
                   for k in ("Hits", "Late", "Misses"))
    # the window's counters: what was consumed after the warm-up
    assert 0 < consumed <= notes["steps"] - FILES + 7
    assert notes["steps"] > 2 * FILES  # several epochs in the window


def test_reference_and_step_agree_and_catch_a_swap_and_a_flipped_byte():
    import jax

    mod = load_module("consumers", "byte_sum_shuffled")
    seed = 2**31 + 3
    ds = bdata.ByteSet(seed, 5, 64 << 10)
    step = mod.make_step(5)

    def run_steps(order_of, n_steps, spoil=None):
        """``n_steps`` steps, epoch ``e`` fed in ``order_of(e)``, the
        device's index array always the REFERENCE order's."""
        slots = jax.numpy.zeros(5, jax.numpy.uint32)
        k = jax.numpy.int32(0)
        for n in range(n_steps):
            e, pos = divmod(n, 5)
            block = ds.file(int(order_of(e)[pos]))
            if spoil == n:
                block = block.copy()
                block[100] ^= 1
            ref = jax.numpy.asarray(
                mod.reference_order(seed, e, 5).astype(np.int32))
            slots, k, _s = step(jax.numpy.asarray(block), slots, ref, k)
        return np.asarray(slots)

    def reference(e):
        return mod.reference_order(seed, e, 5)

    def swapped(e):
        order = reference(e).copy()
        if e == 1:
            order[[0, 3]] = order[[3, 0]]
        return order

    # the reference order is the oracle's contract, every block once
    assert sorted(reference(0)) == list(range(5))
    assert list(reference(0)) != list(reference(1))
    want = mod.reference_slots(ds, seed, 13)  # 2 epochs and 3 steps
    assert np.array_equal(run_steps(reference, 13), want)
    assert not np.array_equal(run_steps(swapped, 13), want)
    assert not np.array_equal(run_steps(reference, 13, spoil=7), want)
    assert not np.array_equal(run_steps(reference, 12), want)
