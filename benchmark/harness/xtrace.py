"""Reduction of a profiler trace (``.xplane.pb``) to what the benchmark
reports: device busy time, the device operations that took most time,
and the idle gaps by what the host was doing in them. Read with
``jax.profiler.ProfileData`` and nothing else; ``tests/`` holds it to a
small recorded trace."""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans an idle gap is attributed to (the loop's own two and the
#: program's two loader spans)
HOST_SPANS = ("bench.wait_input", "bench.dispatch_step",
              "atpu.loader.host_read", "atpu.loader.h2d")
NO_SPAN = "_no_span_"


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


class Trace:
    """Events of one trace as plain tuples ``(name, start_s, dur_s)``:
    ``device_ops[plane]``, ``device_modules[plane]`` and ``host[name]``
    (every host thread's events of that name)."""

    def __init__(self, path: str) -> None:
        from jax.profiler import ProfileData

        self.device_ops: dict = {}
        self.device_modules: dict = {}
        self.host: dict = {n: [] for n in HOST_SPANS}
        for plane in ProfileData.from_file(path).planes:
            if DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        self.device_ops[plane.name] = _events(line)
                    elif line.name == MODULES_LINE:
                        self.device_modules[plane.name] = _events(line)
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in self.host:
                            self.host[e.name].append(
                                (e.name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9))

    def clip(self, t0: float, t1: float) -> None:
        """Keep only what lies inside ``[t0, t1)`` (events cut at the
        edges)."""
        def cut(evs):
            return [(n, max(s, t0), min(s + d, t1) - max(s, t0))
                    for n, s, d in evs if s < t1 and s + d > t0]

        self.device_ops = {k: cut(v) for k, v in self.device_ops.items()}
        self.device_modules = {k: cut(v)
                               for k, v in self.device_modules.items()}
        self.host = {k: cut(v) for k, v in self.host.items()}


def short_name(hlo: str) -> str:
    """``%psum.7 = u8[..] all-reduce(..)`` -> ``psum.7_all-reduce``: a
    device event is named by its whole HLO instruction; keep the
    instruction's name and opcode (the names PR 22's ledger lines
    carry). Anything else is returned as it is."""
    m = re.match(r"%?(\S+) = (.*)", hlo, re.S)
    if not m:
        return hlo
    name, rest = m.groups()
    if rest.startswith("("):  # a tuple type: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    opcode = re.match(r"\s*([\w-]+)\(", rest)
    return f"{name}_{opcode.group(1)}" if opcode else name


def _events(line) -> list:
    names: dict = {}
    out = []
    for e in line.events:
        short = names.get(e.name)
        if short is None:
            short = names[e.name] = short_name(e.name)
        out.append((short, e.start_ns * 1e-9, e.duration_ns * 1e-9))
    return out


def matching(trace: Trace, line: str, match: str) -> list:
    """Durations, one list a device plane, of the events of ``line``
    ("XLA Modules" or "XLA Ops") whose name matches ``match``."""
    by_plane = trace.device_modules if line == MODULES_LINE \
        else trace.device_ops
    rx = re.compile(match)
    return [[d for n, _s, d in evs if rx.search(n)]
            for evs in by_plane.values()]


def union(intervals) -> list:
    """Sorted, disjoint ``[(start, end), ...]`` covering the same time."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def overlap(a, b) -> float:
    """Seconds covered by both of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(busy, t0: float, t1: float) -> list:
    """The complement of ``busy`` (sorted, disjoint) within ``[t0, t1)``."""
    out, at = [], t0
    for s, e in busy:
        if s > at:
            out.append((at, min(s, t1)))
        at = max(at, e)
        if at >= t1:
            break
    if at < t1:
        out.append((at, t1))
    return out


def window_of(trace: Trace) -> tuple:
    """The traced window on the trace's own clock: from the first to the
    last host span of the benchmark's loop. Device work outside it
    (profiler start-up, the drain after the last step) is clipped."""
    marks = [ev for n in ("bench.wait_input", "bench.dispatch_step")
             for ev in trace.host.get(n, [])]
    if not marks:
        raise ValueError("trace holds no bench.* host span")
    return (min(s for _n, s, _d in marks),
            max(s + d for _n, s, d in marks))


def reduce(trace: Trace, top: int = 10) -> dict:
    """``busy_s`` (union of device-op intervals, averaged over the
    device planes), ``window_s``, the ``top`` device operations by
    summed time (averaged over planes) and the idle time of the first
    device plane attributed to each host span."""
    t0, t1 = window_of(trace)
    trace.clip(t0, t1)
    if not trace.device_ops:
        raise ValueError("trace holds no device plane with XLA ops")
    busy_by_plane = {p: union((s, s + d) for _n, s, d in evs)
                     for p, evs in trace.device_ops.items()}
    n_planes = len(busy_by_plane)
    busy_s = sum(sum(e - s for s, e in b)
                 for b in busy_by_plane.values()) / n_planes
    by_op: dict = {}
    for evs in trace.device_ops.values():
        for n, _s, d in evs:
            by_op[n] = by_op.get(n, 0.0) + d / n_planes
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    first = sorted(busy_by_plane)[0]
    idle = gaps(busy_by_plane[first], t0, t1)
    covered = []
    idle_gaps = []
    for name, evs in trace.host.items():
        spans = union((s, s + d) for _n, s, d in evs)
        covered.extend(spans)
        secs = overlap(idle, spans)
        if secs > 0:
            idle_gaps.append((name, secs))
    idle_total = sum(e - s for s, e in idle)
    bare = idle_total - overlap(idle, union(covered))
    if bare > 0:
        idle_gaps.append((NO_SPAN, bare))
    idle_gaps.sort(key=lambda kv: -kv[1])
    return {"busy_s": busy_s, "window_s": t1 - t0,
            "device_ops": [[n, s] for n, s in device_ops],
            "idle_gaps": [[n, s] for n, s in idle_gaps[:top]]}
