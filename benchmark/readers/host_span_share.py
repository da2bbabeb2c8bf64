"""Time covered by one of the program's spans as a share, in %, of the
traced window (``window_s``); events are cut at the window's edges and
overlapping events of several threads count once."""

from benchmark.readers._host_spans import clipped


def read(ctx, *, span: str):
    covered = clipped(ctx, span)
    if not covered or not ctx["window_s"]:
        return None
    return 100.0 * sum(e - s for s, e in covered) / ctx["window_s"]
