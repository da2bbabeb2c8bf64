"""Share, in %, of one group of the program's counters in another, over
the traced part of the window. A name ending in ``*`` is a prefix."""


def _total(counters: dict, names) -> float:
    total = 0
    for want in names:
        for name, v in counters.items():
            if name == want or (want.endswith("*")
                                and name.startswith(want[:-1])):
                total += v
    return total


def read(ctx, *, num, den):
    d = _total(ctx["counters"], den)
    return 100.0 * _total(ctx["counters"], num) / d if d else None
