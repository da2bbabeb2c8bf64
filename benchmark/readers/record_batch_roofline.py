"""The record iterator's programs against the HBM roofline, in %: the
least any implementation can move, which is every byte it hands on read
once and written once (2 x the traced window's ``bytes_counter``, counted
by the program where it yields), over the summed device time of every
event of ``line`` whose name matches ``match`` in the traced window,
against the peak of this ``device_kind`` in ``peaks.json``. The count
does not change with the implementation; the time does. It can read over
100% only if a program of the iterator escapes the name. A program
without the counter or the name (the parent): nothing."""

from benchmark.harness.xtrace import matching


def bytes_needed(yielded_bytes: int) -> int:
    """Each yielded byte is read from its block once and written into
    its batch once."""
    return 2 * yielded_bytes


def read(ctx, *, bytes_counter: str, line: str, match: str,
         peak: str = "hbm_gbps"):
    yielded = ctx["counters"].get(bytes_counter)
    device_s = sum(d for plane in matching(ctx["trace"], line, match)
                   for d in plane)
    if not yielded or not device_s:
        return None
    return 100.0 * bytes_needed(yielded) / device_s \
        / (ctx["peaks"][peak] * 1e9)
