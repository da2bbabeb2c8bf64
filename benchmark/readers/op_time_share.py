"""Device time of the operations whose name matches ``match`` as a
share, in %, of the device's busy time (both averaged over the chips)."""

import re


def read(ctx, *, match: str):
    rx = re.compile(match)
    planes = ctx["trace"].device_ops
    if not planes or not ctx["busy_s"]:
        return None
    secs = sum(d for evs in planes.values() for n, _s, d in evs
               if rx.search(n)) / len(planes)
    return 100.0 * secs / ctx["busy_s"]
