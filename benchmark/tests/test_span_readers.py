"""The readers of the program's own spans, counters and role timers, on
a small trace recorded on the CPU (``data/spans.xplane.pb``: 8 blocks of
64 KiB scanned through a two-block tier of an in-process cluster, the
``bench.*`` loop spans around it) and against tiny
role processes. Counts and arithmetic only, never a speed."""

import json
import os
import shutil
import statistics
import types

import pytest

from benchmark.harness import discover, xtrace
from benchmark.harness.roles import Roles

HERE = os.path.dirname(__file__)
RECORDED = os.path.join(HERE, "data", "spans.xplane.pb")
BLOCK = 64 << 10


def read_metric(name, ctx):
    entry = discover.load_json("layer_metrics", name)
    reader = discover.load_module("readers", entry["reader"])
    return reader.read(ctx, **entry.get("args", {}))


def recorded_events(name):
    from jax.profiler import ProfileData

    return sorted((e.start_ns * 1e-9, e.duration_ns * 1e-9)
                  for plane in ProfileData.from_file(RECORDED).planes
                  for line in plane.lines for e in line.events
                  if e.name == name)


class FakeTrace(xtrace.Trace):
    def __init__(self, ops, host):  # no file
        self.device_ops, self.device_modules, self.host = ops, {}, host


@pytest.fixture()
def ctx(tmp_path):
    """What ``run.py`` hands a reader, with the recorded trace where the
    run's own would lie and a device that is busy exactly while the
    FIRST and the LAST ``host_read`` run (the CPU has no device plane)."""
    where = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    shutil.copy(RECORDED, where / "host.xplane.pb")
    trace = xtrace.Trace(RECORDED)
    reads = recorded_events("atpu.loader.host_read")
    ops = {"/device:TPU:0": [("sum", s, d) for s, d in (reads[0], reads[-1])]}
    t0, t1 = xtrace.window_of(trace)
    roles = types.SimpleNamespace(base=str(tmp_path))
    return {"trace": FakeTrace(ops, trace.host), "window_s": t1 - t0,
            "counters": {}, "consumer": types.SimpleNamespace(roles=roles)}


def test_recorded_trace_holds_the_programs_spans_nested(ctx):
    reads = recorded_events("atpu.loader.host_read")
    opens = recorded_events("atpu.loader.open_block")
    faults = recorded_events("atpu.loader.prefault")
    leases = recorded_events("atpu.shm.lease")
    assert len(reads) == len(opens) == len(faults) == len(leases) == 8

    def inside(inner, outer):
        return outer[0] <= inner[0] and \
            inner[0] + inner[1] <= outer[0] + outer[1] + 1e-9

    for r, o, f, le in zip(reads, opens, faults, leases):
        assert inside(o, r) and inside(f, r) and inside(le, o)
        assert o[0] + o[1] <= f[0]  # disjoint: open_block, then prefault


def test_host_span_ms_is_the_median_of_the_events_in_the_window(ctx):
    for metric, span in (("transport.open_block_ms", "atpu.loader.open_block"),
                         ("transport.lease_ms", "atpu.shm.lease"),
                         ("h2d.prefault_ms", "atpu.loader.prefault")):
        want = statistics.median(d for _s, d in recorded_events(span)) * 1e3
        assert read_metric(metric, ctx) == pytest.approx(want)
    # the parts lie inside the whole the accepted metric reads
    whole = statistics.median(
        d for _n, _s, d in ctx["trace"].host["atpu.loader.host_read"]) * 1e3
    assert read_metric("h2d.prefault_ms", ctx) < whole


def test_host_span_share_is_covered_time_over_the_window(ctx):
    t0, t1 = xtrace.window_of(ctx["trace"])
    waits = recorded_events("atpu.loader.get_wait")
    covered = sum(min(s + d, t1) - max(s, t0) for s, d in waits
                  if s < t1 and s + d > t0)
    got = read_metric("loader.get_wait_share", ctx)
    assert got == pytest.approx(100.0 * covered / (t1 - t0))
    assert 0 < got < 100


def test_span_idle_share_is_idle_time_under_the_span(ctx):
    # the device is busy under the first and the last host_read only, so
    # the idle time under open_block + prefault is that of the other six
    t0, t1 = xtrace.window_of(ctx["trace"])
    reads = recorded_events("atpu.loader.host_read")
    idle_s = (t1 - t0) - reads[0][1] - reads[-1][1]
    opens = recorded_events("atpu.loader.open_block")[1:-1]
    faults = recorded_events("atpu.loader.prefault")[1:-1]
    assert read_metric("idle.open_block_share", ctx) == pytest.approx(
        100.0 * sum(d for _s, d in opens) / idle_s)
    assert read_metric("idle.prefault_share", ctx) == pytest.approx(
        100.0 * sum(d for _s, d in faults) / idle_s)
    assert read_metric("idle.open_block_share", ctx) \
        + read_metric("idle.prefault_share", ctx) <= 100


def test_counter_time_share_and_evictions_per_adopt(ctx):
    ctx["counters"] = {"Client.JaxProducerBlockedUs": 250_000,
                       "Client.JaxHbmEvictions": 6,
                       "Client.JaxHbmAdopts": 8,
                       "Client.JaxShortCircuitBlocks": 9}
    ctx["window_s"] = 1.0
    assert read_metric("loader.producer_blocked_share", ctx) == \
        pytest.approx(25.0)
    assert read_metric("hbm.evictions_per_adopt", ctx) == pytest.approx(75.0)
    # a program without the store's own counters (the parent): nothing
    ctx["counters"] = {"Client.JaxShortCircuitBlocks": 9}
    assert read_metric("hbm.evictions_per_adopt", ctx) is None
    # a counter that stood still reads 0 where the program has it, and
    # nothing where it has not
    from alluxio_tpu.metrics import metrics

    ctx["counters"] = {}
    metrics().counter("Client.JaxProducerBlockedUs")
    assert read_metric("loader.producer_blocked_share", ctx) == 0.0
    reader = discover.load_module("readers", "counter_time_share")
    assert reader.read(ctx, counter="Client.JaxNoSuchCounterUs") is None


def test_a_program_without_the_spans_reads_nothing_and_does_not_raise(
        tmp_path):
    # the parent's trace: PR 24's recording has none of the new spans
    where = tmp_path / "trace" / "plugins" / "profile" / "2026_01_01"
    where.mkdir(parents=True)
    old = os.path.join(HERE, "data", "tiny.xplane.pb")
    shutil.copy(old, where / "host.xplane.pb")
    roles = types.SimpleNamespace(base=str(tmp_path), address="localhost:1",
                                  worker_port=1)
    ctx = {"trace": xtrace.Trace(old), "window_s": 0.4, "counters": {},
           "consumer": types.SimpleNamespace(roles=roles)}
    for name in ("transport.open_block_ms", "transport.lease_ms",
                 "h2d.prefault_ms", "loader.get_wait_share",
                 "idle.prefault_share", "idle.open_block_share",
                 "hbm.evictions_per_adopt", "worker.shm_open_serve_ms",
                 "worker.blocks_displaced", "master.get_status_serve_ms"):
        assert read_metric(name, ctx) is None, name
    # no trace at all
    ctx = {"trace": ctx["trace"], "window_s": 0.4, "counters": {},
           "consumer": types.SimpleNamespace(roles=types.SimpleNamespace(
               base=str(tmp_path / "nowhere")))}
    assert read_metric("h2d.prefault_ms", ctx) is None


def test_role_metric_pulls_the_roles_own_timers_and_counters(tmp_path):
    """Tiny role processes (``tests/data/tiny``'s 1 MiB blocks): one
    file written and read back through the SHM route, then the three
    role metrics."""
    with open(os.path.join(HERE, "data", "tiny", "configs",
                           "tiny-seqread.json")) as f:
        block = json.load(f)["block_bytes"]
    roles = Roles(str(tmp_path), mem_bytes=16 * block,
                  block_bytes=block).start()
    try:
        fs = roles.file_system()
        fs.write_all("/f", b"\x07" * block)
        for _ in range(3):
            fs.get_status("/f")
        with fs.open_file("/f") as f:
            assert f.read(block) == b"\x07" * block
        fs.close()
        ctx = {"consumer": types.SimpleNamespace(roles=roles)}
        master_ms = read_metric("master.get_status_serve_ms", ctx)
        lease_ms = read_metric("worker.shm_open_serve_ms", ctx)
        assert 0 < master_ms < 1e3 and 0 < lease_ms < 1e3
        assert read_metric("worker.blocks_displaced", ctx) == 0
    finally:
        roles.stop()


# ---- what this PR may and may not do to BENCHMARK.json ---------------------
NEW_METRICS = [
    "transport.open_block_ms", "transport.lease_ms", "h2d.prefault_ms",
    "loader.get_wait_share", "loader.producer_blocked_share",
    "hbm.evictions_per_adopt", "idle.prefault_share",
    "idle.open_block_share", "worker.shm_open_serve_ms",
    "worker.blocks_displaced", "master.get_status_serve_ms"]


def test_the_accepted_benchmark_is_untouched_and_only_grows_at_the_end():
    """``data/accepted_pr24.json`` is a literal copy of what the driver
    accepted with PR 24. A program PR may append entries to the lists
    and nothing else (PR 26 was refused, ``benchmark_edited``, for giving
    accepted entries a ``workloads`` list): the next accidental edit
    fails here, on the CPU, and not at the driver."""
    with open(os.path.join(HERE, "data", "accepted_pr24.json")) as f:
        accepted = json.load(f)
    spec = discover.benchmark_json()
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end"):
        assert spec[key] == accepted[key], key
    n = len(accepted["per_layer"])
    assert n == 16 and spec["per_layer"][:n] == accepted["per_layer"]
    added = spec["per_layer"][n:]
    assert [m["name"] for m in added] == NEW_METRICS
    for m in added:
        # like the accepted ones: no ``workloads`` list, so a later cell
        # needs no edit of them; a reader that finds nothing says None
        assert sorted(m) == ["better", "layer", "moves", "name", "source",
                             "unit"], m
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
        entry = discover.load_json("layer_metrics", m["name"])
        assert hasattr(discover.load_module("readers", entry["reader"]),
                       "read")
