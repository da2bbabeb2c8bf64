"""Share, in %, of a parent span's time that named child spans cover:
the time of the parent's events, cut at the traced window's edges, that
lies under the union of the children's events, over the parent's time.
100 minus it is what the parent still does under no name. Every
thread's events go together (``_host_spans.clipped``), so where two
threads run the parent at once a child of one can cover unnamed time of
the other: an UPPER BOUND there, exact for one thread. A program
without the parent span, a run without a trace: nothing."""

from benchmark.harness import xtrace
from benchmark.readers._host_spans import clipped


def read(ctx, *, parent: str, children):
    held = clipped(ctx, parent)
    held_s = sum(e - s for s, e in held)
    if not held_s:
        return None
    named = xtrace.union(iv for c in children for iv in clipped(ctx, c))
    return 100.0 * xtrace.overlap(held, named) / held_s
