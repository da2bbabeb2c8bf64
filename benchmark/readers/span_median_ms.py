"""Median duration, in ms, of the host spans of one name in the traced
window (the program's ``utils/tracing.annotate`` spans land in the
profiler's trace as TraceAnnotations)."""

import statistics


def read(ctx, *, span: str):
    durs = [d for _n, _s, d in ctx["trace"].host.get(span, [])]
    return statistics.median(durs) * 1e3 if durs else None
