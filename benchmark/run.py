#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One client process (this one) owns the chip(s); master and worker are
role processes started first and never on the chip. Set-up (roles,
ingest, tier warm-up, compile or cache load, warm-up steps) is timed as
``setup_s``; then the cell's closed loop runs for ``--seconds``; then,
where the traffic asks for them, cold job starts; then the checks
against the seed's plain reference. Progress and every itemised number
go to earlier ``[bench] <tag> {json}`` lines; the LAST line of stdout
is the one JSON object the contract names. No TPU, too few chips or a
device kind the peak table does not know: non-zero exit, no result.

What a cell is made of is found by name (``harness/discover.py``);
``README.md`` shows how to add a piece.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import shutil
import signal
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import discover, estimators  # noqa: E402
from benchmark.harness.discover import BENCH_DIR  # noqa: E402

SHM = "/dev/shm"
NULL = contextlib.nullcontext()


def say(tag: str, **facts) -> None:
    print(f"[bench] {tag} " + json.dumps(facts, sort_keys=True), flush=True)


def process_age_s() -> float:
    """Seconds since this process was started, from /proc: set-up is
    counted from process start, interpreter and imports included."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


class CompileLog:
    """Counts compile requests (cache hits included: tracing and
    lowering were paid) through JAX's own monitoring events
    (``chip_smoke.CompileLog``'s events)."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.compiles = self.cache_hits = 0
        self.seconds = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs

    def mark(self) -> tuple:
        return (self.compiles, self.seconds, self.cache_hits)


def place_compile_cache(jax) -> str:
    """The persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else the fixed ``.jax_cache``
    of this checkout (the path is part of the key); every compile is
    stored, however short."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return placed


def client_counters() -> dict:
    from alluxio_tpu.metrics import metrics

    return {k: v for k, v in metrics().snapshot().items()
            if k.startswith(("Client.Jax", "Client.BytesRead."))}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


class Window:
    """The cell's closed loop: ask for an item, dispatch its step, keep
    ``depth`` steps in flight, stamp a step done when its token is
    ready. ``waits`` holds the seconds every ask took."""

    def __init__(self, consumer, items, depth: int) -> None:
        self.consumer, self.items, self.depth = consumer, items, depth
        self.done: list = []   # [(t_done, nbytes)]
        self.waits: list = []  # [wait_s] of every ask
        self.asked = 0
        self._inflight = collections.deque()
        self.annotate = None   # TraceAnnotation while a trace is on

    def _span(self, name: str):
        return self.annotate(name) if self.annotate else NULL

    def run(self, seconds: float, *, mark_at=None, on_mark=None) -> None:
        now = time.perf_counter
        self.t0 = now()
        while True:
            ta = now()
            if ta - self.t0 >= seconds:
                break
            if on_mark is not None and ta - self.t0 >= mark_at:
                self.drain()
                on_mark()
                on_mark = None
                continue
            self.asked += 1
            with self._span("bench.wait_input"):
                item = next(self.items)
            tb = now()
            with self._span("bench.dispatch_step"):
                token, nbytes = self.consumer.step(item)
            self.waits.append(tb - ta)
            self._inflight.append((token, nbytes))
            # stamp what is ready now; wait only to keep the depth
            while self._inflight and (
                    len(self._inflight) > self.depth
                    or self._inflight[0][0].is_ready()):
                self._pop()
        self.drain()
        self.t_end = now()

    def warm(self, n: int) -> None:
        """The first ``n`` items through the same loop, untimed: tier
        fill and every shape the window will use."""
        for _ in range(n):
            token, _nbytes = self.consumer.step(next(self.items))
            self._inflight.append((token, 0))
            while len(self._inflight) > self.depth:
                self._pop()
        self.drain()
        self.done.clear()

    def _pop(self) -> None:
        token, nbytes = self._inflight.popleft()
        token.block_until_ready()
        self.done.append((time.perf_counter(), nbytes))

    def drain(self) -> None:
        while self._inflight:
            self._pop()


def run_cell(args, *, spec, configs_dir=BENCH_DIR, traffic_dir=BENCH_DIR,
             peaks_path=os.path.join(BENCH_DIR, "peaks.json"),
             platform="tpu", shm=SHM) -> dict:
    """Everything but argument parsing and the final print; returns the
    final line's object. ``platform`` and the directories are arguments
    so that ``tests/`` can rehearse a tiny cell on the CPU; the command
    line has no way to set them."""
    cell = discover.cell(spec, args.workload)
    config = discover.load_json("configs", cell["config"], configs_dir)
    traffic = discover.load_json("traffic", cell["traffic"], traffic_dir)
    consumer_mod = discover.load_module("consumers", config["consumer"])
    with open(peaks_path) as f:
        peaks_table = json.load(f)

    import jax  # importing is not initialising: the chip is still free

    if platform not in (jax.config.jax_platforms or platform):
        raise SystemExit(
            f"the benchmark needs a {platform}: JAX is pinned to "
            f"{jax.config.jax_platforms!r}; no chip, no result")

    from benchmark.harness import data as bdata
    from benchmark.harness.roles import Roles, mem_tier_bytes

    setup = {}
    base = tempfile.mkdtemp(prefix="atpu_bench_", dir=shm)
    roles = None
    try:
        # roles FIRST, before this process touches the chip
        t0 = time.perf_counter()
        set_bytes = traffic["files"] * config["block_bytes"]
        roles = Roles(base, block_bytes=config["block_bytes"],
                      mem_bytes=mem_tier_bytes(set_bytes)).start()
        setup["roles_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        devices = jax.devices()
        n_chips = cell["chips"]
        if devices[0].platform != platform or len(devices) < n_chips:
            raise SystemExit(
                f"the benchmark needs {n_chips} {platform} chip(s): JAX "
                f"found {len(devices)} x {devices[0].platform!r} "
                f"({devices[0].device_kind}); no chip, no result")
        kind = devices[0].device_kind
        if kind not in peaks_table:
            raise SystemExit(
                f"no peaks on record for device_kind={kind!r}; add it to "
                f"{peaks_path} with its source")
        devices = devices[:n_chips]
        cache_dir = place_compile_cache(jax)
        compiles = CompileLog()
        setup["jax_init_s"] = time.perf_counter() - t0
        say("start", workload=cell["name"], seed=args.seed,
            seconds=args.seconds, trace=args.trace, platform=platform,
            device_kind=kind, device_count=len(devices), jax=jax.__version__,
            compile_cache=cache_dir, roles=roles.assert_off_chip())

        consumer = consumer_mod.Consumer(
            config=config, traffic=traffic, seed=args.seed,
            devices=devices, roles=roles)
        fs = roles.file_system()

        t0 = time.perf_counter()
        write_runs = bdata.ingest(
            fs, consumer.dataset, write_type=config["write_type"],
            threads=config["writer_threads"], runs=traffic["write_runs"])
        setup["ingest_s"] = time.perf_counter() - t0
        write_rates = estimators.run_rates(write_runs)
        say("ingest", files=consumer.dataset.n_files,
            bytes=consumer.dataset.total_bytes,
            run_gbps=[r / 1e9 for r in write_rates])

        # the window's loader or mesh cache, then every shape warm
        t0 = time.perf_counter()
        consumer.open(fs)
        setup["open_s"] = time.perf_counter() - t0
        setup.update(getattr(consumer, "setup_items", {}))
        window = Window(consumer, consumer.inputs(), traffic["inflight"])
        window.warm(consumer.warm_items)
        setup["warm_s"] = time.perf_counter() - t0

        probes = {}
        trace_dir = None
        if args.trace:
            t0 = time.perf_counter()
            for name in traffic.get("probes", []):
                probes.update(getattr(consumer, f"probe_{name}")())
            say("probes", **probes)
            trace_dir = os.path.join(base, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            window.annotate = jax.profiler.TraceAnnotation
            setup["probes_s"] = time.perf_counter() - t0
        setup["compile_s"] = compiles.seconds
        setup["compiles"] = compiles.compiles
        setup["compile_cache_hits"] = compiles.cache_hits
        setup_s = process_age_s()
        say("setup", setup_s=setup_s, **setup)

        # ---- the measured window ------------------------------------------
        c0, m0 = client_counters(), compiles.mark()
        traced = {}

        def end_trace() -> None:
            # the traced part of the window is over: counters and loop
            # samples of the per-layer metrics stop here
            traced["counters"] = delta(client_counters(), c0)
            traced["n"] = len(window.waits)
            traced["seconds"] = time.perf_counter() - window.t0
            window.annotate = None
            jax.profiler.stop_trace()

        if args.trace:
            window.run(args.seconds, on_mark=end_trace,
                       mark_at=min(args.seconds,
                                   traffic.get("trace_seconds", 4)))
            if not traced:  # the window ended before the mark
                end_trace()
        else:
            window.run(args.seconds)
        m1 = compiles.mark()
        counters = delta(client_counters(), c0)
        seconds = window.t_end - window.t0
        total = estimators.total_rate(window.done, window.t0, window.t_end)
        slices = estimators.slice_rates(window.done, window.t0,
                                        int(seconds))
        say("window", seconds=seconds, steps=len(window.done),
            total_gbps=total / 1e9,
            slice_gbps=[s / 1e9 for s in slices],
            compiles=m1[0] - m0[0], compile_s=m1[1] - m0[1],
            counters=counters)
        hbm = consumer.close_window()
        if hbm and hbm.get("hbm_bytes", 0) > config["hbm_bytes"]:
            raise AssertionError(f"HBM store over capacity: {hbm}")

        # ---- cold job starts (outside the window and outside set-up) ------
        cold, warmed = [], False
        want = traffic.get("cold_starts")
        if want:
            t0 = time.perf_counter()
            while len(cold) < want["min"] or (
                    time.perf_counter() - t0 < want["seconds"]
                    and len(cold) < want["max"]):
                cold.append(consumer.cold_start())
            first = [c["first_batch_ms"] for c in cold]
            warmed = estimators.trend_down(first)
            say("cold_starts", n=len(cold), first_batch_ms=first,
                min_ms=min(first), max_ms=max(first),
                loader_ctor_ms=[c["loader_ctor_ms"] for c in cold],
                trend_down=warmed)

        # ---- checks against the plain reference, fetched once --------------
        t0 = time.perf_counter()
        checked = consumer.check()
        say("check", seconds=time.perf_counter() - t0, hbm=hbm, **checked)
        attempted = consumer.warm_items + window.asked \
            + consumer.dataset.n_files + len(cold)
        # a "cold" start that got warm is a fault of the yardstick
        failed = checked["failed"] + (1 if warmed else 0)
        fs.close()
        say("roles_end", roles=roles.assert_off_chip())

        values = {"step_gbps": total / 1e9, "setup_s": setup_s}
        if traffic.get("report_write"):
            values["write_gbps"] = sum(nb for nb, _w in write_runs) \
                / sum(w for _nb, w in write_runs) / 1e9
        if cold:
            values["first_batch_ms"] = statistics.median(
                c["first_batch_ms"] for c in cold)
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(devices),
                  "memory_peak_bytes": max(
                      (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)}
        result = {"correct": failed == 0, "attempted": attempted,
                  "failed": failed, "device": device}
        if not args.trace:
            result["metrics"] = pick(spec, "end_to_end", cell, values)
            return result

        from benchmark.harness import xtrace

        trace = xtrace.Trace(xtrace.newest_xplane(trace_dir))
        reduced = xtrace.reduce(trace)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
        n = traced["n"]
        waits = window.waits[:n]
        traced_slices = slices[:int(traced["seconds"])]
        ctx = {
            "trace": trace, "peaks": peaks_table[kind],
            "busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
            "counters": traced["counters"], "consumer": consumer,
            "values": {
                **{f"setup.{k}": v for k, v in setup.items()},
                **{f"probe.{k}": v for k, v in probes.items()
                   if not isinstance(v, list)},
                "loop.input_wait_share":
                    100.0 * sum(waits) / traced["seconds"],
                "loop.stall_p95_ms":
                    1e3 * estimators.percentile(waits, 95),
                "loop.slice_median_gbps":
                    statistics.median(traced_slices) / 1e9
                    if traced_slices else None,
                "loop.compiles_in_window": m1[0] - m0[0],
                "cold.get_status_ms": statistics.median(
                    [s for c in cold for s in c["get_status_ms"]])
                if cold else None,
                "cold.loader_ctor_ms": statistics.median(
                    c["loader_ctor_ms"] for c in cold) if cold else None,
            }}
        layer_values = {}
        for m in discover.metrics_of(spec, "per_layer", cell["name"]):
            entry = discover.load_json("layer_metrics", m["name"])
            reader = discover.load_module("readers", entry["reader"])
            layer_values[m["name"]] = reader.read(ctx, **entry.get("args", {}))
        result["metrics"] = pick(spec, "per_layer", cell, layer_values)
        return result
    finally:
        if roles is not None:
            roles.stop()
        shutil.rmtree(base, ignore_errors=True)


def pick(spec: dict, group: str, cell: dict, values: dict) -> dict:
    """``{name: {"value", "unit"}}`` for the metrics of ``group`` this
    cell reports; one whose reader found nothing is left out."""
    out = {}
    for m in discover.metrics_of(spec, group, cell["name"]):
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = discover.benchmark_json()
    if args.seconds is None:
        args.seconds = float(spec["run_seconds"])
    # a run that is told to end still stops its roles and empties /dev/shm
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    result = run_cell(args, spec=spec)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
