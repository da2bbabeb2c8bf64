"""Median device duration, in ms, of the events of one trace line
("XLA Modules" or "XLA Ops") whose name matches ``match``, over every
device plane."""

import statistics

from benchmark.harness.xtrace import matching


def read(ctx, *, line: str, match: str):
    durs = [d for plane in matching(ctx["trace"], line, match)
            for d in plane]
    return statistics.median(durs) * 1e3 if durs else None
