"""The command itself: no TPU, no result; nothing but the benchmark's
own files, no result."""

import json
import os
import shutil
import subprocess
import sys

from benchmark.harness.discover import BENCH_DIR, ROOT

ARGS = ["--workload", "seqread-32m.scan-16g", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _json_lines(out: str) -> list:
    found = []
    for line in out.splitlines():
        try:
            found.append(json.loads(line))
        except ValueError:
            pass
    return found


def test_no_tpu_means_non_zero_and_no_json_line():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "7"}
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), *ARGS],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert _json_lines(p.stdout) == []
    assert "no chip, no result" in p.stderr


def test_alone_with_benchmark_json_it_prints_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_PLATFORMS")}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert _json_lines(p.stdout) == []
    assert "alluxio_tpu" in p.stderr  # the program is not there
    assert not [d for d in os.listdir("/dev/shm")
                if d.startswith("atpu_bench_")]


def test_unknown_workload_lists_the_cells():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"),
         "--workload", "nope", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and _json_lines(p.stdout) == []
    assert "seqread-32m.scan-16g" in p.stderr
