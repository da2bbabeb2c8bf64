"""Multi-process test cluster: real OS processes per role.

Re-design of ``minicluster/src/main/java/alluxio/multi/process/
MultiProcessCluster.java:94`` (+ ``PortCoordination``): spawns each
master/worker as a separate ``python -m alluxio_tpu.shell.main <role>``
subprocess configured via ``ATPU_*`` env vars, with kill/restart of
individual processes for failover tests (the crash-recovery analogue of
``LimitedLifeMasterProcess``)."""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, List, Optional

from alluxio_tpu.rpc.clients import FsMasterClient, MetaMasterClient
from alluxio_tpu.utils.exceptions import AlluxioTpuError


_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ManagedProcess:
    """One spawned role process."""

    def __init__(self, role: str, env: Dict[str, str],
                 log_path: str) -> None:
        self.role = role
        self.env = env
        self.log_path = log_path
        self.proc: Optional[subprocess.Popen] = None

    def start(self) -> None:
        log = open(self.log_path, "ab")
        # role processes never touch the accelerator: a chip belongs to
        # one process, and that process is the client
        env = {**os.environ, **self.env, "JAX_PLATFORMS": "cpu"}
        # what bin/alluxio-tpu does: the package resolves wherever the
        # caller's working directory is
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (_PKG_ROOT, env.get("PYTHONPATH")) if p)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "alluxio_tpu.shell.main", self.role],
            env=env, stdout=log, stderr=subprocess.STDOUT)

    def kill(self, sig: int = signal.SIGKILL) -> None:
        """Hard-kill (crash simulation)."""
        if self.proc is not None and self.proc.poll() is None:
            self.proc.send_signal(sig)
            self.proc.wait(timeout=10)

    def stop(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=5)

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None


class MultiProcessCluster:
    """N masters (shared journal dir -> file-lock election) + M workers,
    each a real subprocess."""

    def __init__(self, base_dir: str, *, num_masters: int = 1,
                 num_workers: int = 1,
                 journal_type: str = "LOCAL",
                 extra_conf: Optional[Dict[str, str]] = None) -> None:
        """``journal_type``: LOCAL = shared journal dir + flock election
        (masters must share a filesystem); EMBEDDED = per-master journal
        dirs + Raft quorum over the embedded journal ports (true
        multi-host HA; reference: EmbeddedJournalIntegrationTest)."""
        self.base = base_dir
        self.journal_dir = os.path.join(base_dir, "journal")
        self.journal_type = journal_type.upper()
        self.master_ports = [free_port() for _ in range(num_masters)]
        self.raft_ports = [free_port() for _ in range(num_masters)]
        self.worker_ports = [free_port() for _ in range(num_workers)]
        self.masters: List[ManagedProcess] = []
        self.workers: List[ManagedProcess] = []
        self._extra = dict(extra_conf or {})
        os.makedirs(self.journal_dir, exist_ok=True)
        os.makedirs(os.path.join(base_dir, "logs"), exist_ok=True)

    # -- addresses -----------------------------------------------------------
    @property
    def master_addresses(self) -> str:
        return ",".join(f"localhost:{p}" for p in self.master_ports)

    def _common_env(self) -> Dict[str, str]:
        env = {
            "ATPU_HOME": self.base,
            "ATPU_MASTER_JOURNAL_FOLDER": self.journal_dir,
            "ATPU_MASTER_HOSTNAME": "localhost",
            "ATPU_MASTER_SAFEMODE_WAIT": "0s",
        }
        for k, v in self._extra.items():
            env["ATPU_" + str(k).replace("atpu.", "").replace(".", "_")
                .upper()] = str(v)
        return env

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "MultiProcessCluster":
        for i, port in enumerate(self.master_ports):
            self.start_master(i)
        self.wait_for_primary()
        for i in range(len(self.worker_ports)):
            self.start_worker(i)
        self.wait_for_workers(len(self.worker_ports))
        return self

    @property
    def raft_addresses(self) -> str:
        return ",".join(f"127.0.0.1:{p}" for p in self.raft_ports)

    def start_master(self, index: int) -> ManagedProcess:
        env = self._common_env()
        env["ATPU_MASTER_RPC_PORT"] = str(self.master_ports[index])
        env["ATPU_MASTER_HA_ENABLED"] = "true"
        if self.journal_type == "EMBEDDED":
            env["ATPU_MASTER_JOURNAL_TYPE"] = "EMBEDDED"
            # each quorum member keeps its OWN journal (no shared fs)
            env["ATPU_MASTER_JOURNAL_FOLDER"] = os.path.join(
                self.base, f"journal-m{index}")
            env["ATPU_MASTER_EMBEDDED_JOURNAL_ADDRESSES"] = \
                self.raft_addresses
            env["ATPU_MASTER_EMBEDDED_JOURNAL_ADDRESS"] = \
                f"127.0.0.1:{self.raft_ports[index]}"
        p = ManagedProcess(
            "master", env,
            os.path.join(self.base, "logs", f"master{index}.out"))
        p.start()
        if index < len(self.masters):
            self.masters[index] = p
        else:
            self.masters.append(p)
        return p

    def start_worker(self, index: int) -> ManagedProcess:
        wdir = os.path.join(self.base, f"worker{index}")
        env = {
            # defaults a caller's extra_conf may override
            "ATPU_WORKER_RAMDISK_SIZE": "64MB",
            "ATPU_WORKER_BLOCK_HEARTBEAT_INTERVAL": "200ms",
            **self._common_env(),
            # HA: workers address the full master list and fail over
            "ATPU_MASTER_RPC_ADDRESSES": self.master_addresses,
            "ATPU_WORKER_RPC_PORT": str(self.worker_ports[index]),
            "ATPU_WORKER_DATA_FOLDER": wdir,
            "ATPU_WORKER_SHM_DIR": os.path.join(wdir, "shm"),
            "ATPU_WORKER_HOSTNAME": "localhost",
            "ATPU_TIERED_IDENTITY": f"host=localhost-w{index}",
        }
        p = ManagedProcess(
            "worker", env,
            os.path.join(self.base, "logs", f"worker{index}.out"))
        p.start()
        if index < len(self.workers):
            self.workers[index] = p
        else:
            self.workers.append(p)
        return p

    # -- readiness -----------------------------------------------------------
    def wait_for_primary(self, timeout_s: float = 180.0) -> str:
        """Block until some master serves RPCs; returns its address."""
        deadline = time.monotonic() + timeout_s
        last_err: Optional[Exception] = None
        while time.monotonic() < deadline:
            for port in self.master_ports:
                try:
                    MetaMasterClient(f"localhost:{port}",
                                     retry_duration_s=0.2).get_master_info()
                    return f"localhost:{port}"
                except (AlluxioTpuError, Exception) as e:  # noqa: BLE001
                    last_err = e
            time.sleep(0.2)
        raise TimeoutError(f"no primary master within {timeout_s}s: "
                           f"{last_err}")

    def primary_index(self, timeout_s: float = 180.0) -> int:
        """Index of the master currently serving RPCs (the address
        format and port list are this class's own invariants — callers
        must not re-parse them)."""
        addr = self.wait_for_primary(timeout_s)
        return self.master_ports.index(int(addr.rsplit(":", 1)[1]))

    def wait_for_workers(self, count: int, timeout_s: float = 60.0) -> None:
        from alluxio_tpu.rpc.clients import BlockMasterClient

        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                c = BlockMasterClient(self.master_addresses,
                                      retry_duration_s=1.0)
                if len(c.get_worker_infos()) >= count:
                    return
            except Exception:  # noqa: BLE001
                pass
            time.sleep(0.2)
        raise TimeoutError(f"{count} workers not registered in {timeout_s}s")

    # -- clients -------------------------------------------------------------
    def fs_client(self) -> FsMasterClient:
        return FsMasterClient(self.master_addresses)

    def file_system(self):
        from alluxio_tpu.client.file_system import FileSystem
        from alluxio_tpu.conf import Configuration

        return FileSystem(self.master_addresses,
                          conf=Configuration(load_env=False))

    # -- teardown ------------------------------------------------------------
    def stop(self) -> None:
        for p in self.workers + self.masters:
            p.stop()

    def __enter__(self) -> "MultiProcessCluster":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False
