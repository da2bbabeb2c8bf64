"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding tests run
without TPU hardware (the driver separately dry-run-compiles the multi-chip
path via ``__graft_entry__.dryrun_multichip``).
"""

import os

# force CPU even when the ambient environment names an accelerator
# (jax may already be imported by a plugin: config.update wins then)
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except ImportError:
    pass

import pytest  # noqa: E402

# Always-on lock-order auditing + hang watchdog (see
# alluxio_tpu/lint/pytest_lockaudit.py): master/worker/store locks are
# auto-instrumented and any observed lock-order inversion fails the test.
pytest_plugins = ("alluxio_tpu.lint.pytest_lockaudit",)


@pytest.fixture()
def conf(tmp_path):
    """A fresh Configuration rooted in a temp dir."""
    from alluxio_tpu.conf import Configuration, Keys

    c = Configuration(load_env=False)
    c.set(Keys.HOME, str(tmp_path))
    c.set(Keys.MASTER_JOURNAL_FOLDER, str(tmp_path / "journal"))
    c.set(Keys.MASTER_METASTORE_DIR, str(tmp_path / "metastore"))
    c.set(Keys.WORKER_DATA_FOLDER, str(tmp_path / "worker"))
    c.set(Keys.WORKER_SHM_DIR, str(tmp_path / "shm"))
    c.set(Keys.USER_CLIENT_CACHE_DIR, str(tmp_path / "client_cache"))
    c.set(Keys.MASTER_BACKUP_DIR, str(tmp_path / "backups"))
    return c


@pytest.fixture(autouse=True)
def _reset_heartbeats():
    from alluxio_tpu.heartbeat import HeartbeatScheduler, HeartbeatThread

    yield
    HeartbeatThread.reset_timer_policy()
    HeartbeatScheduler.clear()


def pytest_runtest_protocol(item, nextitem):
    """Bounded rerun for ``steal_prone`` tests: the CI container's CPU
    is shared and stolen in multi-second bursts (observed 3-4x
    slowdowns mid-round), which flakes the real-subprocess election /
    kill-recovery tests on pure timing. A marked test that fails gets
    exactly ONE fresh run; a genuine failure still fails twice and
    surfaces. Unmarked tests are untouched."""
    if item.get_closest_marker("steal_prone") is None:
        return None
    from _pytest.runner import runtestprotocol

    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    first_failed = [r for r in reports if r.failed]
    if first_failed:
        # only the FINAL attempt is logged: logging the first failure
        # would count the test failed even when the rerun passes
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
        # the first attempt's traceback must not vanish — an
        # intermittently-real bug that passes on retry has to stay
        # visible (render with -rA, or via CI report consumers).
        # Attach to the call report, or the last report when the rerun
        # died in setup and produced no call phase.
        target = next((r for r in reports if r.when == "call"),
                      reports[-1] if reports else None)
        if target is not None:
            target.sections.append(
                ("steal_prone first-attempt failure",
                 "\n".join(str(f.longrepr) for f in first_failed)))
    for r in reports:
        item.ihook.pytest_runtest_logreport(report=r)
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True
