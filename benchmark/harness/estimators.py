"""The arithmetic between samples and metrics. Pure functions of lists,
so ``tests/`` holds them to recorded samples."""

from __future__ import annotations

import statistics


def percentile(values, p: float) -> float:
    """Nearest-rank percentile (p in 0..100) of a non-empty list."""
    s = sorted(values)
    k = max(0, min(len(s) - 1, -(-len(s) * p // 100) - 1))
    return s[int(k)]


def slice_rates(done, t0: float, seconds: float, width: float = 1.0):
    """Bytes per second in each WHOLE ``width``-second slice of the
    window ``[t0, t0 + seconds)``. ``done`` is ``[(t_completed, nbytes),
    ...]`` in completion order; a step's bytes accrue evenly between the
    completion before it and its own, so a slice boundary that falls
    inside a step splits that step's bytes instead of rounding a slice
    up or down by one whole step."""
    n = int(seconds // width)
    out = [0.0] * n
    prev = t0
    for t, nbytes in done:
        lo, hi = prev - t0, t - t0
        prev = t
        if hi <= lo:  # stamped in the same instant: all in one slice
            k = int(hi // width)
            if 0 <= k < n:
                out[k] += nbytes
            continue
        k = max(0, int(lo // width))
        while k < n and k * width < hi:
            part = min(hi, (k + 1) * width) - max(lo, k * width)
            if part > 0:
                out[k] += nbytes * part / (hi - lo)
            k += 1
    return [b / width for b in out]


def total_rate(done, t0: float, t_end: float) -> float:
    """All the bytes of completed steps over all the time of the window."""
    return sum(nb for _t, nb in done) / (t_end - t0)


def run_rates(runs):
    """``[(nbytes, wall_s), ...]`` -> bytes/s of each run."""
    return [nb / wall for nb, wall in runs]


def trend_down(samples, ratio: float = 0.85) -> bool:
    """Cold starts that get faster are not cold: true when the median of
    the later half is under ``ratio`` of the median of the earlier half
    (the middle sample of an odd count belongs to neither)."""
    h = len(samples) // 2
    if h < 2:
        return False
    return statistics.median(samples[-h:]) < \
        ratio * statistics.median(samples[:h])
