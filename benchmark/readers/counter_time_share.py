"""A counter of the program that adds up time (``unit_s`` seconds a
count: ``...Us`` = 1e-6) as a share, in %, of the traced window
(``window_s``). A counter that did not move in the window reads 0 if the
program has it at all, and nothing if it does not."""


def read(ctx, *, counter: str, unit_s: float = 1e-6):
    count = ctx["counters"].get(counter)
    if count is None:
        from alluxio_tpu.metrics import metrics

        if counter not in metrics().snapshot():
            return None
        count = 0
    if not ctx["window_s"]:
        return None
    return 100.0 * count * unit_s / ctx["window_s"]
