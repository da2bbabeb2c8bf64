"""The whole mesh step against its roofline, in %: the least time a chip
needs for one step, the larger of the HBM bytes it has to move over its
HBM peak and the ICI bytes it has to move over its ICI peak (the
consumer's ``step_floor_bytes``, computed from shapes by functions kept
with the consumer; the peaks of this ``device_kind`` in ``peaks.json``),
over the median device time of the step's module on ``line``. Every
chip runs the same program on its own shard and is held to its own
peaks; the median is over all the chips' events together. It is the
share of the WHOLE step, so it can read over 100% only if the bytes are
counted too high or the module's time leaves out part of the step. A
consumer without the bytes, a trace without the module: nothing."""

from benchmark.readers import device_event_ms


def floor_s(need: dict, peaks: dict) -> float:
    """Seconds the chip cannot do a step under, and what binds it."""
    return max(need["hbm"] / (peaks["hbm_gbps"] * 1e9),
               need["ici"] / (peaks["ici_gbit_s"] / 8 * 1e9))


def read(ctx, *, line: str, match: str):
    need = getattr(ctx["consumer"], "step_floor_bytes", None)
    step_ms = device_event_ms.read(ctx, line=line, match=match)
    if not need or not step_ms:
        return None
    return 100.0 * floor_s(need, ctx["peaks"]) * 1e3 / step_ms
