"""A number the harness itself took by the host's clock or counted:
set-up items, loop waits, cold-start timings, probes, compile events."""


def read(ctx, *, key: str):
    return ctx["values"].get(key)
