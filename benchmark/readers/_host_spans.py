"""What the span readers share: the program's own spans (``atpu.*``) out
of the run's ``.xplane.pb``. ``harness/xtrace.Trace`` keeps only its
four ``HOST_SPANS``, so this opens the trace itself, once a run (cached
on ``ctx``), and clips to the traced window (``xtrace.window_of``). A
program without the span, a run without a trace: nothing, never an
error. Not a reader (the leading ``_`` keeps it out of the listing)."""

from __future__ import annotations

import os

from benchmark.harness import xtrace

PREFIX = "atpu."


def host_spans(ctx) -> dict:
    """``{"window": (t0, t1), "spans": {name: [(start_s, dur_s), ...]}}``
    of every ``atpu.*`` event on the host planes that touches the
    window, as recorded (not cut at the window's edges)."""
    got = ctx.get("_host_spans")
    if got is not None:
        return got
    got = ctx["_host_spans"] = {"window": None, "spans": {}}
    try:
        from jax.profiler import ProfileData

        path = xtrace.newest_xplane(
            os.path.join(ctx["consumer"].roles.base, "trace"))
        t0, t1 = got["window"] = xtrace.window_of(ctx["trace"])
        planes = ProfileData.from_file(path).planes
    except (AttributeError, KeyError, OSError, ValueError):
        return got
    for plane in planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                    if s < t1 and s + d > t0:
                        got["spans"].setdefault(e.name, []).append((s, d))
    return got


def clipped(ctx, span: str) -> list:
    """Sorted, disjoint ``[(start, end)]`` of one span's events, cut at
    the window's edges (every thread's events together)."""
    got = host_spans(ctx)
    if not got["spans"].get(span):
        return []
    t0, t1 = got["window"]
    return xtrace.union((max(s, t0), min(s + d, t1))
                        for s, d in got["spans"][span])
