"""Each consumer and its plain reference, tiny, on the CPU: results
only. The whole-cell cases go through ``run_cell`` with real role
processes and ``tests/data/tiny``'s sizes (1 MiB blocks)."""

import argparse
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.harness import data as bdata
from benchmark.harness.discover import load_module

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
with open(os.path.join(TINY, "BENCHMARK.json")) as f:
    TINY_SPEC = json.load(f)


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.hot", {"step_gbps", "write_gbps", "setup_s"}),
    ("tiny.scan", {"step_gbps", "first_batch_ms", "setup_s"}),
    ("tiny.records", {"step_gbps", "first_batch_ms", "setup_s"}),
    ("tiny.mesh", {"step_gbps", "setup_s"}),
])
def test_cell_runs_tiny_and_is_correct(cell, metrics, tmp_path, capsys):
    args = argparse.Namespace(workload=cell, seed=2**31 + 11, seconds=1.0,
                              trace=0)
    result = run.run_cell(
        args, spec=TINY_SPEC, configs_dir=TINY, traffic_dir=TINY,
        peaks_path=os.path.join(TINY, "peaks.json"), platform="cpu",
        shm=str(tmp_path))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == metrics
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["attempted"] > 0
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert os.listdir(tmp_path) == []  # roles stopped, nothing left
    tags = [line.split(" ", 2)[1] for line in
            capsys.readouterr().out.splitlines() if line.startswith("[bench]")]
    assert tags[:3] == ["start", "ingest", "setup"] and "check" in tags


def test_byte_sum_reference_and_step_agree_and_catch_a_flipped_byte():
    import jax

    byte_sum = load_module("consumers", "byte_sum")
    ds = bdata.ByteSet(2**31 + 3, 3, 64 << 10)
    for i in range(3):
        assert ds.byte_sum(i) == int(ds.file(i).sum(dtype=np.uint64)) \
            & 0xFFFFFFFF
    step = byte_sum.make_step(3)
    slots, k = jax.numpy.zeros(3, jax.numpy.uint32), jax.numpy.int32(0)
    for n in range(5):  # 5 steps: files 0 and 1 twice, file 2 once
        slots, k, _s = step(jax.numpy.asarray(ds.file(n % 3)), slots, k)
    assert np.array_equal(np.asarray(slots),
                          byte_sum.reference_slots(ds, 5))
    bad = ds.file(0).copy()
    bad[100] ^= 1
    slots, k, _s = step(jax.numpy.asarray(bad), slots, k)
    assert not np.array_equal(np.asarray(slots),
                              byte_sum.reference_slots(ds, 6))


def test_train_linear_step_matches_the_numpy_reference():
    import jax

    tl = load_module("consumers", "train_linear")
    ds = bdata.RecordSet(5, 2, 1 << 20)
    recs = ds.records(0)[:32]
    params = tl.init_params(5, ds.record_bytes - bdata.LABEL_BYTES)
    _p, nonfinite, loss, lab = tl.make_step()(
        jax.device_put(params), jax.numpy.asarray(recs),
        jax.numpy.int32(0))
    assert int(nonfinite) == 0
    assert np.array_equal(np.asarray(lab), ds.labels(0)[:32])
    assert abs(float(loss) - tl.reference_loss(recs, params)) <= tl.LOSS_TOL
    # the reference is not vacuous: shuffled labels move the loss
    wrong = recs.copy()
    wrong[:, :4] = np.roll(wrong[:, :4], 1, axis=0)
    assert abs(tl.reference_loss(wrong, params)
               - tl.reference_loss(recs, params)) > tl.LOSS_TOL


def test_record_stream_labels_cross_shards():
    ds = bdata.RecordSet(9, 3, 1 << 20)
    got = ds.stream_labels(ds.per_file - 5, 12)
    assert np.array_equal(got[:5], ds.labels(0)[-5:])
    assert np.array_equal(got[5:], ds.labels(1)[:7])
    assert np.array_equal(ds.file(2)[:4].view("<i4"), ds.labels(2)[:1])


def test_mesh_index_table_takes_the_same_from_every_owner():
    mesh = load_module("consumers", "mesh_batch_sum")
    a = mesh.index_table(1, 256, 4, 8)
    b = mesh.index_table(2, 256, 4, 8)
    assert a.shape == (mesh.TABLE_ROWS, 8) and not np.array_equal(a, b)
    for table in (a, b):
        owners = np.sort(table // 64, axis=1)
        assert (owners == np.array([0, 0, 1, 1, 2, 2, 3, 3])).all()
        assert all(len(set(row)) == 8 for row in table.tolist())
    assert mesh.sum_bytes_needed(8, 32 << 20) == 256 << 20
