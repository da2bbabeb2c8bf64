"""A number out of a ROLE process's own metrics registry, pulled at the
end of the run: the master's ``get_metrics`` or the worker's (the sum of
``names``, times ``scale``), one pull a role a run (cached on ``ctx``).
Whole-run: set-up, window and cold starts take the same path through the
role, and the role cannot tell them apart. A role that does not serve
the call, or has none of the names: nothing."""


def _snapshot(ctx, role: str) -> dict:
    snaps = ctx.setdefault("_role_snapshots", {})
    if role not in snaps:
        roles = ctx["consumer"].roles
        try:
            if role == "master":
                from alluxio_tpu.rpc.clients import MetaMasterClient

                snaps[role] = MetaMasterClient(
                    roles.address, retry_duration_s=1.0).get_metrics()
            else:
                from alluxio_tpu.rpc.clients import WorkerClient

                snaps[role] = WorkerClient(
                    f"localhost:{roles.worker_port}",
                    retry_duration_s=1.0).get_metrics()
        except Exception:  # noqa: BLE001 a program without the pull
            snaps[role] = {}
    return snaps[role]


def read(ctx, *, role: str, names, scale: float = 1.0):
    snap = _snapshot(ctx, role)
    found = [snap[n] for n in names if n in snap]
    return scale * sum(found) if found else None
