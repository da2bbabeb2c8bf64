"""A kernel's share of its bandwidth roofline, in %: the bytes the
algorithm needs for each call (the consumer's ``step_bytes_needed``,
computed from shapes by a function kept with the consumer) over the
device time of the matching events, against the peak of this
``device_kind`` in ``peaks.json``. Each chip is held to its own peak;
the share is over all the chips' events together."""

from benchmark.harness.xtrace import matching


def read(ctx, *, line: str, match: str, peak: str = "hbm_gbps"):
    durs = [d for plane in matching(ctx["trace"], line, match)
            for d in plane]
    if not durs or not sum(durs):
        return None
    need = ctx["consumer"].step_bytes_needed * len(durs)
    return 100.0 * need / sum(durs) / (ctx["peaks"][peak] * 1e9)
