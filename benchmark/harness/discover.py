"""Finds what a cell is made of by NAME: there is no registry to edit.

``BENCHMARK.json`` names cells, configurations and metrics; every other
piece is a file of its own that is found by listing its directory:

    configs/<config>.json        the deployment as it is run
    traffic/<traffic>.json       the parameters one generator reads
    consumers/<name>.py          named by the config's "consumer"
    layer_metrics/<metric>.json  names a reader and its arguments
    readers/<reader>.py          ``read(ctx, **args) -> number | None``

An unknown name is an error that lists the known ones.
"""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class UnknownName(SystemExit):
    """Exit non-zero naming the kind, the name asked for and the names
    that exist."""

    def __init__(self, kind: str, name: str, known) -> None:
        super().__init__(f"unknown {kind} {name!r}; known: "
                         f"{', '.join(sorted(known)) or '(none)'}")


def _names(subdir: str, ext: str, base: str = BENCH_DIR) -> dict:
    d = os.path.join(base, subdir)
    return {f[:-len(ext)]: os.path.join(d, f) for f in sorted(os.listdir(d))
            if f.endswith(ext) and not f.startswith("_")}


def load_json(subdir: str, name: str, base: str = BENCH_DIR) -> dict:
    known = _names(subdir, ".json", base)
    if name not in known:
        raise UnknownName(subdir, name, known)
    with open(known[name]) as f:
        return json.load(f)


def load_module(subdir: str, name: str, base: str = BENCH_DIR):
    known = _names(subdir, ".py", base)
    if name not in known:
        raise UnknownName(subdir, name, known)
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{subdir}_{name}", known[name])
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark_json(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(spec: dict, name: str) -> dict:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise UnknownName("workload", name, cells)
    return cells[name]


def metrics_of(spec: dict, group: str, cell_name: str) -> list:
    """The metrics of ``group`` that this cell reports: an entry with no
    ``workloads`` key belongs to every cell."""
    return [m for m in spec[group]
            if cell_name in m.get("workloads", [cell_name])]
