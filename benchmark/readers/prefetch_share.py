"""Share, in %, of one group of the prefetch service's counters in
another over the WHOLE window: ``run.py`` keeps only ``Client.Jax*`` and
``Client.BytesRead.*`` for ``ctx["counters"]``, so the consumer
snapshots ``Client.Prefetch*`` itself when its warm-up ends and when the
window closes (``consumer.prefetch_window``). A consumer without the
snapshot, a window that consumed nothing: nothing, never an error."""

from benchmark.readers import counter_share


def read(ctx, *, num, den):
    window = getattr(ctx["consumer"], "prefetch_window", None)
    if not window:
        return None
    return counter_share.read({"counters": window}, num=num, den=den)
