"""Median duration, in ms, of one of the program's spans
(``tracer().span``: every span of a process that has imported jax is a
``TraceAnnotation`` on the host plane of the profiler's trace) over the
events that start inside the traced window."""

import statistics

from benchmark.readers._host_spans import host_spans


def read(ctx, *, span: str):
    got = host_spans(ctx)
    evs = got["spans"].get(span)
    if not evs:
        return None
    t0, t1 = got["window"]
    durs = [d for s, d in evs if t0 <= s < t1]
    return statistics.median(durs) * 1e3 if durs else None
