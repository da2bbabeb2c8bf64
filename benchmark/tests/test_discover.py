"""Data-file discovery: every name ``BENCHMARK.json`` uses resolves to a
file of its own, and an unknown name is an error listing the known."""

import os

import pytest

from benchmark.harness import discover

SPEC = discover.benchmark_json()


def test_every_cell_resolves_to_its_files():
    for cell in SPEC["workloads"]:
        config = discover.load_json("configs", cell["config"])
        traffic = discover.load_json("traffic", cell["traffic"])
        mod = discover.load_module("consumers", config["consumer"])
        assert hasattr(mod, "Consumer")
        assert traffic["files"] > 0 and traffic["inflight"] >= 1


def test_config_entries_name_their_files_and_sources():
    for c in SPEC["configs"]:
        on_disk = discover.load_json("configs", c["name"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert on_disk["source"] == c["source"]
        assert on_disk["reduced"] == c["reduced"]
        assert on_disk["guarantees"] and on_disk["assumed"]


def test_every_layer_metric_has_a_file_naming_a_reader():
    ends = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        entry = discover.load_json("layer_metrics", m["name"])
        reader = discover.load_module("readers", entry["reader"])
        assert callable(reader.read)
        assert m["moves"] in ends


def test_metrics_of_keeps_a_metric_to_the_cells_it_lists():
    spec = {"end_to_end": [
        {"name": "step_gbps"}, {"name": "setup_s"},
        {"name": "write_gbps", "workloads": ["a.hot"]},
        {"name": "first_batch_ms", "workloads": ["a.scan", "b.train"]}]}
    names = lambda cell: [m["name"] for m in discover.metrics_of(  # noqa: E731
        spec, "end_to_end", cell)]
    assert names("a.hot") == ["step_gbps", "setup_s", "write_gbps"]
    assert names("a.scan") == ["step_gbps", "setup_s", "first_batch_ms"]
    assert names("c.mesh") == ["step_gbps", "setup_s"]


@pytest.mark.parametrize("call,kind,known", [
    (lambda: discover.cell(SPEC, "nope.cell"), "workload",
     "seqread-32m.scan-16g"),
    (lambda: discover.load_json("traffic", "nope"), "traffic",
     "epoch-scan-16g"),
    (lambda: discover.load_json("configs", "nope"), "configs",
     "imagenet64-train"),
    (lambda: discover.load_module("consumers", "nope"), "consumers",
     "byte_sum"),
    (lambda: discover.load_module("readers", "nope"), "readers",
     "span_median_ms"),
    (lambda: discover.load_json("layer_metrics", "nope"), "layer_metrics",
     "hbm.hit_share"),
])
def test_unknown_name_lists_the_known_ones(call, kind, known):
    with pytest.raises(SystemExit) as e:
        call()
    msg = str(e.value)
    assert f"unknown {kind} 'nope" in msg and known in msg
    assert e.value.code != 0


def test_a_new_file_is_found_without_editing_anything(tmp_path):
    d = tmp_path / "traffic"
    d.mkdir()
    (d / "epoch-new.json").write_text('{"files": 3, "inflight": 1}')
    assert discover.load_json("traffic", "epoch-new",
                              str(tmp_path))["files"] == 3
    assert os.path.isdir(os.path.join(discover.BENCH_DIR, "traffic"))
