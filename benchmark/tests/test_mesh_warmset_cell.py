"""The four-chip warm-set cell rehearsed tiny on four CPU devices (16 x
1 MiB through ``run_cell`` with real role processes), its bytes and its
roofline reader: results, counts and arithmetic only, never a speed."""

import argparse
import json
import os
import types

import pytest

from benchmark import run
from benchmark.harness import discover, xtrace
from benchmark.harness.discover import load_module

CELL = "mesh4-warmset-32m.gather-b8-16g"
TINY_PEAKS = os.path.join(os.path.dirname(__file__), "data", "tiny",
                          "peaks.json")


def _tiny(tmp_path):
    """The committed configuration and traffic cut to 16 x 1 MiB, and a
    spec that names them as the committed cell does."""
    config = discover.load_json("configs", "mesh4-warmset-32m")
    traffic = discover.load_json("traffic", "gather-b8-16g")
    config["block_bytes"] = 1 << 20
    traffic.update(files=16, warm_steps=2, write_runs=2,
                   cold_starts={"min": 2, "seconds": 0, "max": 2})
    for sub, name, body in (("configs", "mesh4-warmset-32m", config),
                            ("traffic", "gather-b8-16g", traffic)):
        os.makedirs(tmp_path / "pieces" / sub)
        with open(tmp_path / "pieces" / sub / f"{name}.json", "w") as f:
            json.dump(body, f)
    return discover.benchmark_json(), str(tmp_path / "pieces")


def test_the_cell_runs_tiny_and_is_correct(tmp_path, capsys):
    spec, pieces = _tiny(tmp_path)
    shm = tmp_path / "shm"
    shm.mkdir()
    args = argparse.Namespace(workload=CELL, seed=2**31 + 33, seconds=1.0,
                              trace=0)
    result = run.run_cell(args, spec=spec, configs_dir=pieces,
                          traffic_dir=pieces, peaks_path=TINY_PEAKS,
                          platform="cpu", shm=str(shm))
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"step_gbps", "first_batch_ms",
                                      "setup_s"}
    assert result["device"]["count"] == 4
    assert os.listdir(shm) == []  # roles stopped, nothing left
    lines = {line.split(" ", 2)[1]: json.loads(line.split(" ", 2)[2])
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("[bench]")}
    assert lines["cold_starts"]["n"] == 2
    notes = lines["check"]["notes"]
    assert notes["cold_starts"] == 2 and notes["all_gather_free"] is True
    assert notes["placement_wrong"] == notes["cold_rows_wrong"] == 0
    # the program's own split of the set-up's load, as seconds
    for item in ("load_global_s", "mesh_host_read_s", "mesh_stack_s",
                 "mesh_put_s"):
        assert lines["setup"][item] > 0


def test_the_cells_entries_are_found_by_name():
    spec = discover.benchmark_json()
    cell = discover.cell(spec, CELL)
    assert cell["chips"] == 4
    names = {m["name"] for m in discover.metrics_of(spec, "per_layer", CELL)}
    for name in names:  # every metric of the cell has a file and a reader
        entry = discover.load_json("layer_metrics", name)
        assert hasattr(load_module("readers", entry["reader"]), "read")
    assert {"ici.collective_share", "mesh.step_ms", "mesh.step_roofline",
            "mesh.load_global_s", "mesh.host_read_s", "mesh.stack_s",
            "mesh.put_s"} <= names
    # what reads the loader's window is not this cell's
    assert not names & {"h2d.host_read_ms", "transport.lease_ms",
                        "hbm.hit_share", "loader.get_wait_share"}
    assert {m["name"] for m in
            discover.metrics_of(spec, "end_to_end", CELL)} \
        == {"step_gbps", "first_batch_ms", "setup_s"}


def test_step_bytes_are_the_issues_reckoning():
    mesh = load_module("consumers", "mesh_warmset")
    mib32 = 32 << 20
    assert mesh.hbm_bytes_needed(8, 4, mib32) == (2 + 8 + 8) * mib32
    assert mesh.ici_bytes_needed(8, 4, mib32) == 2 * 3 * (256 << 20) // 4
    assert mesh.ici_bytes_needed(8, 1, mib32) == 0  # one chip: no exchange


class FakeTrace(xtrace.Trace):
    def __init__(self, modules):  # no file
        self.device_ops, self.device_modules, self.host = {}, modules, {}


def test_mesh_step_roofline_is_the_floor_over_the_median_step():
    reader = load_module("readers", "mesh_step_roofline")
    with open(os.path.join(discover.BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)["TPU v5 lite"]
    need = {"hbm": 18 * (32 << 20), "ici": 384 << 20}
    floor = reader.floor_s(need, peaks)
    assert floor == pytest.approx((384 << 20) / 200e9)  # ICI-bound
    assert floor > need["hbm"] / 819e9
    mods = {f"/device:TPU:{d}": [("jit_bench_mesh_batch_sum", 0.0, 0.008),
                                 ("jit_bench_mesh_batch_sum", 0.1, 0.010),
                                 ("jit_other", 0.2, 0.5)]
            for d in range(4)}
    ctx = {"trace": FakeTrace(mods), "peaks": peaks,
           "consumer": types.SimpleNamespace(step_floor_bytes=need)}
    args = discover.load_json("layer_metrics", "mesh.step_roofline")["args"]
    assert reader.read(ctx, **args) == pytest.approx(100 * floor / 0.009)
    # a consumer without the bytes, a trace without the module: nothing
    assert reader.read({**ctx, "consumer": object()}, **args) is None
    assert reader.read({**ctx, "trace": FakeTrace({})}, **args) is None
