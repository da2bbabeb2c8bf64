"""The roofline reader of the record iterator's programs on a synthetic
``ctx``: arithmetic only, never a speed."""

import json
import os

import pytest

from benchmark.harness import discover, xtrace

HERE = os.path.dirname(__file__)
METRIC = "batch.assemble_roofline"


class FakeTrace(xtrace.Trace):
    def __init__(self, modules):  # no file
        self.device_ops, self.device_modules, self.host = {}, modules, {}


def read_metric(ctx):
    entry = discover.load_json("layer_metrics", METRIC)
    reader = discover.load_module("readers", entry["reader"])
    return reader.read(ctx, **entry.get("args", {}))


@pytest.fixture()
def peaks():
    with open(os.path.join(HERE, "..", "peaks.json")) as f:
        return json.load(f)["TPU v5 lite"]


def test_roofline_is_twice_the_yielded_bytes_over_the_programs_time(peaks):
    # three block programs of 1 ms, one tail program of 0.5 ms and a
    # train step that must not count
    modules = {"/device:TPU:0": [
        ("jit_atpu_record_batch", 0.0, 1e-3),
        ("jit_bench_train_step", 1e-3, 5e-3),
        ("jit_atpu_record_batch", 6e-3, 1e-3),
        ("jit_atpu_record_batch", 8e-3, 1e-3),
        ("jit_atpu_record_batch_tail", 9e-3, 0.5e-3)]}
    yielded = 64 * 128 * 12292
    ctx = {"trace": FakeTrace(modules), "peaks": peaks,
           "counters": {"Client.JaxRecordBatchBytes": yielded,
                        "Client.JaxRecordBatches": 64}}
    want = 100.0 * 2 * yielded / 3.5e-3 / (peaks["hbm_gbps"] * 1e9)
    assert read_metric(ctx) == pytest.approx(want)
    assert 0 < read_metric(ctx) < 100


def test_a_program_without_the_counter_or_the_name_reads_nothing(peaks):
    named = {"/device:TPU:0": [("jit_atpu_record_batch", 0.0, 1e-3)]}
    other = {"/device:TPU:0": [("jit_to_records", 0.0, 1e-3)]}
    # the parent: no counter, no program of that name
    assert read_metric({"trace": FakeTrace(other), "peaks": peaks,
                        "counters": {}}) is None
    assert read_metric({"trace": FakeTrace(named), "peaks": peaks,
                        "counters": {}}) is None
    assert read_metric({"trace": FakeTrace(other), "peaks": peaks,
                        "counters": {"Client.JaxRecordBatchBytes": 1}}) \
        is None
    assert read_metric({"trace": FakeTrace({}), "peaks": peaks,
                        "counters": {"Client.JaxRecordBatchBytes": 1}}) \
        is None


def test_the_new_cell_and_its_metrics_are_found_by_name():
    spec = discover.benchmark_json()
    cell = discover.cell(spec, "imagenet64-train.epoch-b128")
    assert cell["chips"] == 1
    config = discover.load_json("configs", cell["config"])
    assert config["reduced"] == [] and config["consumer"] == "train_linear"
    discover.load_json("traffic", cell["traffic"])
    names = [m["name"] for m in discover.metrics_of(
        spec, "per_layer", cell["name"])]
    for name in ("device.train_step_ms", "batch.assemble_ms",
                 "batch.host_share", "batch.device_ms", METRIC,
                 "setup.compiles"):
        assert name in names
        entry = discover.load_json("layer_metrics", name)
        discover.load_module("readers", entry["reader"])
    # the byte-sum kernel's roofline is scan-16g's alone
    assert "device.sum_bytes_roofline" not in names
    assert len(names) == 27 + 6
