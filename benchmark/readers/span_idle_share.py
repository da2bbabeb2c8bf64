"""Share, in %, of the device's idle time that one of the program's
spans covers: idle = the gaps between the operations of the first
device plane inside the traced window (``xtrace.gaps`` / ``overlap``,
as ``xtrace.reduce`` attributes its ``idle_gaps``), here over ALL the
idle time, so the shares of disjoint spans of one thread add up."""

from benchmark.harness import xtrace
from benchmark.readers._host_spans import clipped, host_spans


def read(ctx, *, span: str):
    covered = clipped(ctx, span)
    ops = ctx["trace"].device_ops
    if not covered or not ops:
        return None
    t0, t1 = host_spans(ctx)["window"]
    busy = xtrace.union((s, s + d) for _n, s, d in ops[sorted(ops)[0]])
    idle = xtrace.gaps(busy, t0, t1)
    idle_s = sum(e - s for s, e in idle)
    return 100.0 * xtrace.overlap(idle, covered) / idle_s if idle_s else None
