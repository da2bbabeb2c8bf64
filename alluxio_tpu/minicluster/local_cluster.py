"""In-process test cluster: master + N workers over real gRPC.

Re-design of ``minicluster/.../LocalAlluxioCluster.java:45`` +
``LocalAlluxioClusterResource``: every role runs as threads in one process,
RPC rides real gRPC on ephemeral ports, tier dirs live under a scratch
directory. Functional tests use this; process-level failover tests use
``multi_process.py`` (reference: ``MultiProcessCluster.java:94``).
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from alluxio_tpu.conf import Configuration, Keys
from alluxio_tpu.master.process import MasterProcess
from alluxio_tpu.rpc.clients import (
    BlockMasterClient, FsMasterClient, MetaMasterClient, WorkerClient,
)
from alluxio_tpu.rpc.worker_service import WorkerEndpoint, serve_worker
from alluxio_tpu.utils.wire import TieredIdentity, WorkerNetAddress
from alluxio_tpu.worker.process import BlockWorker
from alluxio_tpu.worker.ufs_manager import WorkerUfsManager


class _WorkerHandle:
    def __init__(self, worker: BlockWorker, server: WorkerEndpoint):
        self.worker = worker
        self.server = server
        self.port = server.port

    @property
    def address(self) -> str:
        return f"localhost:{self.port}"

    def stop(self) -> None:
        self.worker.stop()
        self.server.stop()


class LocalCluster:
    def __init__(self, base_dir: str, *, num_workers: int = 1,
                 conf_overrides: Optional[Dict] = None,
                 worker_mem_bytes: int = 64 << 20,
                 block_size: int = 1 << 20,
                 start_worker_heartbeats: bool = False,
                 start_job_service: bool = False) -> None:
        self._base = base_dir
        self._num_workers = num_workers
        self._worker_mem = worker_mem_bytes
        self._start_hb = start_worker_heartbeats
        self.conf = Configuration(load_env=False)
        self.conf.set(Keys.HOME, base_dir)
        self.conf.set(Keys.MASTER_JOURNAL_FOLDER,
                      os.path.join(base_dir, "journal"))
        self.conf.set(Keys.MASTER_RPC_PORT, 0)  # ephemeral
        self.conf.set(Keys.USER_BLOCK_SIZE_BYTES_DEFAULT, block_size)
        self.conf.set(Keys.MASTER_SAFEMODE_WAIT, "0s")
        if not start_worker_heartbeats:
            # No heartbeat loop means worker liveness is unknowable: the
            # lost-worker detector would silently expire a healthy worker
            # after the default timeout (and with no heartbeat to carry
            # the re-register command it can never come back). Overrides
            # below still win for tests that drive detection explicitly.
            self.conf.set(Keys.MASTER_WORKER_TIMEOUT, "10000min")
        for k, v in (conf_overrides or {}).items():
            self.conf.set(k, v)
        self.master: Optional[MasterProcess] = None
        self.workers: List[_WorkerHandle] = []
        self._start_job_service = start_job_service
        self.job_master = None
        self.job_workers: List = []

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "LocalCluster":
        root_ufs = os.path.join(self._base, "underFSStorage")
        os.makedirs(root_ufs, exist_ok=True)
        self.master = MasterProcess(self.conf, root_ufs_uri=root_ufs)
        self.master.start()
        for i in range(self._num_workers):
            self._start_worker(i)
        if self._start_job_service:
            self.start_job_service()
        return self

    def _start_worker(self, index: int) -> _WorkerHandle:
        wconf = self.conf.copy()
        wdir = os.path.join(self._base, f"worker{index}")
        wconf.set(Keys.WORKER_DATA_FOLDER, wdir)
        wconf.set(Keys.WORKER_SHM_DIR, os.path.join(wdir, "shm"))
        wconf.set(Keys.WORKER_RAMDISK_SIZE, self._worker_mem)
        wconf.set(Keys.WORKER_HOSTNAME, "localhost")
        # ephemeral per-worker web port: a shared fixed default would
        # EADDRINUSE the second worker when the endpoint is enabled
        wconf.set(Keys.WORKER_WEB_PORT, 0)
        bm_client = BlockMasterClient(self.master.address)
        fs_client = FsMasterClient(self.master.address)
        # distinct locality hosts so policies can tell workers apart
        address = WorkerNetAddress(
            host="localhost", rpc_port=0,
            shm_dir=os.path.join(wdir, "shm"),
            tiered_identity=TieredIdentity.from_spec(
                f"host=localhost-w{index},slice=slice0"))
        worker = BlockWorker(wconf, bm_client, fs_client,
                             ufs_manager=None, address=address,
                             meta_master_client=MetaMasterClient(
                                 self.master.address))
        # UFS resolution must be in place before the RPC server serves a
        # single read (a UFS-descriptor read in the gap would crash on None)
        worker.ufs_manager = WorkerUfsManager(fs_client)
        server = serve_worker(worker, wconf, bind_host="127.0.0.1")
        if self._start_hb:
            worker.start()
        else:
            worker._master_sync.register_with_master()
            worker.maybe_start_web()
        handle = _WorkerHandle(worker, server)
        self.workers.append(handle)
        return handle

    def add_worker(self) -> _WorkerHandle:
        return self._start_worker(len(self.workers))

    def start_job_service(self) -> None:
        """Start a job master + one job worker per block worker
        (reference: job master/worker co-deployment, §3.5 of SURVEY.md)."""
        from alluxio_tpu.job.process import JobMasterProcess, make_job_worker

        jconf = self.conf.copy()
        jconf.set(Keys.JOB_MASTER_RPC_PORT, 0)
        # tight heartbeat so in-process tests converge fast
        jconf.set(Keys.JOB_WORKER_HEARTBEAT_INTERVAL, "50ms")
        self.job_master = JobMasterProcess(jconf, self.master.address)
        self.job_master.start()
        # the metadata master's table service reaches the job master via
        # its conf; propagate the ephemeral port it actually bound
        self.conf.set(Keys.JOB_MASTER_RPC_PORT,
                      int(self.job_master.address.rsplit(":", 1)[1]))
        for i in range(len(self.workers)):
            jw = make_job_worker(jconf, self.job_master.address,
                                 self.master.address, f"localhost-w{i}")
            jw.start()
            self.job_workers.append(jw)
        self.master.attach_replication_checker(self.job_client(),
                                               interval_s=0.1)
        self.master.attach_persistence_scheduler(self.job_client(),
                                                 interval_s=0.1)

    def stop(self) -> None:
        for jw in self.job_workers:
            jw.stop()
        if self.job_master is not None:
            self.job_master.stop()
        for w in self.workers:
            w.stop()
        if self.master is not None:
            self.master.stop()

    def __enter__(self) -> "LocalCluster":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    # -- clients ------------------------------------------------------------
    def fs_client(self) -> FsMasterClient:
        return FsMasterClient(self.master.address)

    def block_client(self) -> BlockMasterClient:
        return BlockMasterClient(self.master.address)

    def meta_client(self) -> MetaMasterClient:
        return MetaMasterClient(self.master.address)

    def worker_client(self, index: int = 0) -> WorkerClient:
        return WorkerClient(self.workers[index].address)

    def job_client(self):
        from alluxio_tpu.rpc.job_service import JobMasterClient

        return JobMasterClient(self.job_master.address)

    def file_system(self):
        """A full FileSystem client bound to this cluster."""
        from alluxio_tpu.client.file_system import FileSystem

        return FileSystem(self.master.address, conf=self.conf)
