"""Master and worker as the OS processes a site runs, started through
the role entry point (``python -m alluxio_tpu.shell.main master|worker``,
what ``bin/alluxio-tpu-start.sh`` execs) and configured by ``ATPU_*``
environment variables. The benchmark's own copy of what
``chip_smoke.start_roles`` / ``MultiProcessCluster`` do, so that a later
change to the program's test cluster cannot move the yardstick.

Roles are started BEFORE the client touches JAX and are pinned to
``JAX_PLATFORMS=cpu``: a chip belongs to one process, the client."""

from __future__ import annotations

import contextlib
import os
import socket
import subprocess
import sys
import time

from benchmark.harness.discover import ROOT

MIB = 1 << 20


def mem_tier_bytes(held: int) -> int:
    """A MEM tier that keeps ``held`` bytes resident: the worker frees a
    tier that passes its 0.95 high watermark down to 0.7, demoting
    blocks to SSD, so the set sits at 0.9 of the tier."""
    return int(held / 0.9) + MIB


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Roles:
    """One master + one worker; ``stop()`` ends both and waits."""

    def __init__(self, base: str, *, mem_bytes: int, block_bytes: int):
        self.base = base
        self.master_port = _free_port()
        self.worker_port = _free_port()
        self.procs: list = []  # [(role, Popen, log_path)]
        os.makedirs(os.path.join(base, "logs"), exist_ok=True)
        os.makedirs(os.path.join(base, "journal"), exist_ok=True)
        self._common = {
            "ATPU_HOME": base,
            "ATPU_MASTER_JOURNAL_FOLDER": os.path.join(base, "journal"),
            "ATPU_MASTER_HOSTNAME": "localhost",
            "ATPU_MASTER_SAFEMODE_WAIT": "0s",
            # the MASTER's default decides the block size of new files
            "ATPU_USER_BLOCK_SIZE_BYTES_DEFAULT": str(block_bytes),
            "ATPU_WORKER_RAMDISK_SIZE": str(mem_bytes),
        }

    @property
    def address(self) -> str:
        return f"localhost:{self.master_port}"

    def _spawn(self, role: str, env: dict) -> None:
        log_path = os.path.join(self.base, "logs", f"{role}.out")
        full = {**os.environ, **self._common, **env, "JAX_PLATFORMS": "cpu"}
        full["PYTHONPATH"] = os.pathsep.join(
            p for p in (ROOT, full.get("PYTHONPATH")) if p)
        with open(log_path, "ab") as log:
            proc = subprocess.Popen(
                [sys.executable, "-m", "alluxio_tpu.shell.main", role],
                env=full, stdout=log, stderr=subprocess.STDOUT)
        self.procs.append((role, proc, log_path))

    def start(self) -> "Roles":
        from alluxio_tpu.rpc.clients import (BlockMasterClient,
                                             MetaMasterClient)

        self._spawn("master", {
            "ATPU_MASTER_RPC_PORT": str(self.master_port),
            "ATPU_MASTER_HA_ENABLED": "true"})
        self._wait(lambda: MetaMasterClient(
            self.address, retry_duration_s=0.2).get_master_info(),
            "master", 180.0)
        wdir = os.path.join(self.base, "worker0")
        self._spawn("worker", {
            "ATPU_WORKER_BLOCK_HEARTBEAT_INTERVAL": "200ms",
            "ATPU_MASTER_RPC_ADDRESSES": self.address,
            "ATPU_WORKER_RPC_PORT": str(self.worker_port),
            "ATPU_WORKER_DATA_FOLDER": wdir,
            "ATPU_WORKER_SHM_DIR": os.path.join(wdir, "shm"),
            "ATPU_WORKER_HOSTNAME": "localhost",
            "ATPU_TIERED_IDENTITY": "host=localhost-w0"})

        def registered():
            infos = BlockMasterClient(
                self.address, retry_duration_s=1.0).get_worker_infos()
            if not infos:
                raise RuntimeError("no worker registered yet")

        self._wait(registered, "worker", 60.0)
        return self

    def _wait(self, probe, what: str, timeout_s: float) -> None:
        deadline = time.monotonic() + timeout_s
        last = None
        while time.monotonic() < deadline:
            self.assert_alive()
            try:
                probe()
                return
            except Exception as e:  # noqa: BLE001 not up yet: ask again
                last = e
            time.sleep(0.1)
        raise TimeoutError(f"{what} not serving in {timeout_s}s: {last}")

    def file_system(self):
        """A new client, as a job makes one (own channels, own SHM
        segment cache)."""
        from alluxio_tpu.client.file_system import FileSystem
        from alluxio_tpu.conf import Configuration

        return FileSystem(self.address, conf=Configuration(load_env=False))

    def assert_alive(self) -> None:
        for role, proc, log_path in self.procs:
            if proc.poll() is not None:
                raise RuntimeError(f"{role} process died; log: {log_path}")

    def assert_off_chip(self) -> list:
        """No role may have opened the accelerator: a live role has
        neither libtpu mapped nor an accelerator device node open."""
        self.assert_alive()
        seen = []
        for role, proc, _log in self.procs:
            pid = proc.pid
            with open(f"/proc/{pid}/maps") as f:
                libtpu = "libtpu" in f.read()
            fds = []
            for fd in os.listdir(f"/proc/{pid}/fd"):
                with contextlib.suppress(OSError):
                    fds.append(os.readlink(f"/proc/{pid}/fd/{fd}"))
            nodes = [t for t in fds
                     if t.startswith(("/dev/accel", "/dev/vfio"))]
            if libtpu or nodes:
                raise RuntimeError(
                    f"{role} (pid {pid}) touched the accelerator: "
                    f"libtpu mapped={libtpu}, device nodes={nodes}")
            seen.append(f"{role}:{pid}")
        return seen

    def stop(self) -> None:
        for _role, proc, _log in reversed(self.procs):
            if proc.poll() is None:
                proc.terminate()
        for _role, proc, _log in reversed(self.procs):
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)
