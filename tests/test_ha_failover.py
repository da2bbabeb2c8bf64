"""Failover-hardened HA control plane (docs/ha.md): standby read
serving + md_version coherence, client master failover (leader-hint
redirects, rotation, standby read routing), the deterministic chaos
harness (FaultPlan + HaCluster), crash-point fencing/durability, the
quorum view (`get_masters` / `fsadmin report masters`), and the
location-drift invalidation push."""

from __future__ import annotations

import io
import random
import time

import pytest

from alluxio_tpu.conf import Configuration, Keys
from alluxio_tpu.journal.ha import FileLockPrimarySelector, MasterRegistry
from alluxio_tpu.master.process import FaultTolerantMasterProcess
from alluxio_tpu.rpc.clients import FsMasterClient, MetaMasterClient
from alluxio_tpu.rpc.core import RpcChannel
from alluxio_tpu.rpc.master_service import FS_SERVICE
from alluxio_tpu.utils import faults
from alluxio_tpu.utils.exceptions import (
    JournalClosedError, NotPrimaryError,
)
from alluxio_tpu.utils.faults import FaultPlan, FaultStep
from alluxio_tpu.utils.retry import ExponentialTimeBoundedRetry, retry


def make_conf(tmp_path, **overrides) -> Configuration:
    c = Configuration(load_env=False)
    c.set(Keys.HOME, str(tmp_path))
    c.set(Keys.MASTER_JOURNAL_FOLDER, str(tmp_path / "journal"))
    c.set(Keys.MASTER_RPC_PORT, 0)
    c.set(Keys.MASTER_SAFEMODE_WAIT, "0s")
    c.set(Keys.MASTER_STANDBY_TAIL_INTERVAL, "50ms")
    c.set(Keys.MASTER_HA_PUBLISH_INTERVAL, "100ms")
    for k, v in overrides.items():
        c.set(k, v)
    return c


def start_primary_standby(tmp_path):
    """A serving primary + a tailing standby over one shared journal
    (file-lock flavor; a selector gate forces the second master to
    stay standby while the first lives — in-process flock is per-pid)."""
    m1 = FaultTolerantMasterProcess(make_conf(tmp_path))
    m1.start()
    assert m1.serving

    class _Gate(FileLockPrimarySelector):
        def try_acquire(self_inner) -> bool:  # noqa: N805
            if m1.serving:
                return False
            return super(_Gate, self_inner).try_acquire()

    m2 = FaultTolerantMasterProcess(
        make_conf(tmp_path), selector=_Gate(str(tmp_path / "journal")))
    m2.start()
    assert not m2.serving
    assert m2.standby_rpc_port, "standby did not open its read endpoint"
    return m1, m2


def wait_until(pred, timeout=15.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------- retry unit
class TestRetryFailoverSatellite:
    def test_full_jitter_spans_the_whole_backoff_band(self):
        """Full jitter sleeps uniform in [0, backoff]: the old
        [backoff/2, backoff] band never produced a sleep under half the
        backoff, which kept failover retries synchronized."""
        sleeps = []
        p = ExponentialTimeBoundedRetry(
            60.0, 1.0, 1.0, sleep_fn=sleeps.append,
            time_fn=lambda: 0.0, rng=random.Random(7))
        for _ in range(40):
            assert p.attempt()
        assert max(sleeps) <= 1.0
        assert min(sleeps) < 0.5, \
            "no sleep below backoff/2 — still half-jitter"

    def test_redirect_consumes_no_attempt_and_no_sleep(self):
        sleeps = []
        p = ExponentialTimeBoundedRetry(
            60.0, 1.0, 1.0, sleep_fn=sleeps.append, time_fn=lambda: 0.0)
        assert p.attempt()
        before = p.attempt_count
        p.note_redirect()
        assert p.attempt()
        assert p.attempt_count == before, "redirect consumed an attempt"
        assert sleeps == [], "redirect slept"

    def test_retry_helper_honors_leader_hint(self):
        sleeps = []
        p = ExponentialTimeBoundedRetry(
            60.0, 1.0, 1.0, sleep_fn=sleeps.append, time_fn=lambda: 0.0)
        calls = []

        def fn():
            calls.append(1)
            if len(calls) == 1:
                raise NotPrimaryError("standby", leader="localhost:1234")
            return "ok"

        assert retry(fn, p) == "ok"
        assert sleeps == [], "leader-hinted retry slept before redirect"

    def test_not_primary_error_round_trips_leader(self):
        e = NotPrimaryError("nope", leader="host:19998")
        d = e.to_wire()
        back = type(e).from_wire(d)
        assert isinstance(back, NotPrimaryError)
        assert back.leader == "host:19998"
        assert back.code == "UNAVAILABLE"  # transparently retryable


# ------------------------------------------------------------ fault plan unit
class TestFaultPlan:
    def test_steps_run_in_schedule_order_with_log(self):
        ran = []
        plan = FaultPlan([
            FaultStep(0.02, "b", tag=2),
            FaultStep(0.0, "a", tag=1),
            FaultStep(0.04, "a", tag=3),
        ])
        log = plan.run({"a": lambda tag: ran.append(("a", tag)) or "ra",
                        "b": lambda tag: ran.append(("b", tag)) or "rb"})
        assert ran == [("a", 1), ("b", 2), ("a", 3)]
        assert [e["action"] for e in log] == ["a", "b", "a"]
        assert all(e["ok"] for e in log)

    def test_unknown_action_rejected_upfront(self):
        with pytest.raises(KeyError):
            FaultPlan([FaultStep(0, "nope")]).run({"a": lambda: None})

    def test_failing_step_surfaces(self):
        def boom():
            raise RuntimeError("chaos failed to chaos")

        with pytest.raises(RuntimeError):
            FaultPlan([FaultStep(0, "boom")]).run({"boom": boom})

    def test_continue_on_error_runs_the_rest_then_raises(self):
        ran = []

        def boom():
            raise RuntimeError("x")

        with pytest.raises(RuntimeError):
            FaultPlan([FaultStep(0, "boom"),
                       FaultStep(0.01, "ok")]).run(
                {"boom": boom, "ok": lambda: ran.append(1)},
                continue_on_error=True)
        assert ran == [1]


# ----------------------------------------------------------- standby serving
class TestStandbyReadServing:
    def test_standby_serves_stamped_reads_rejects_writes(self, tmp_path):
        m1, m2 = start_primary_standby(tmp_path)
        try:
            FsMasterClient(m1.address).create_directory("/served")
            standby = f"localhost:{m2.standby_rpc_port}"
            sc = FsMasterClient(standby, retry_duration_s=10.0,
                                fastpath=False)
            wait_until(lambda: sc.exists("/served"), msg="standby tail")
            info, stamp = sc.get_status("/served", want_version=True)
            assert info.folder and stamp is not None and stamp >= 1
            infos, lstamp = sc.list_status("/", want_version=True)
            assert "/served" in ["/" + i.name for i in infos]
            assert lstamp is not None
            # a WRITE on the raw channel (no client redirect machinery)
            # must come back as a typed NotPrimaryError + leader hint
            with pytest.raises(NotPrimaryError) as ei:
                RpcChannel(standby).call(FS_SERVICE, "create_directory",
                                         {"path": "/nope"})
            assert ei.value.leader == m1.client_address
        finally:
            m2.stop(), m1.stop()

    def test_standby_serves_a_batched_status_call(self, tmp_path):
        """``get_status_many`` is a standby read like ``get_status``:
        served off the tailed state under the standby's own stamp and
        marked, so a strong multi-endpoint client turns it into a
        redirect; a path's own error stays that path's."""
        from alluxio_tpu.utils.exceptions import FileDoesNotExistError

        m1, m2 = start_primary_standby(tmp_path)
        try:
            pc = FsMasterClient(m1.address)
            for name in ("/sa", "/sb"):
                pc.create_directory(name)
            standby = f"localhost:{m2.standby_rpc_port}"
            sc = FsMasterClient(standby, retry_duration_s=10.0,
                                fastpath=False)
            wait_until(lambda: sc.exists("/sb"), msg="standby tail")
            answers, stamp = sc.get_status_many(
                ["/sb", "/absent", "/sa"], want_version=True)
            assert [a.path for a in answers[::2]] == ["/sb", "/sa"]
            assert isinstance(answers[1], FileDoesNotExistError)
            assert stamp == m2.fs_master.invalidations.version >= 1
            raw = RpcChannel(standby).call(
                FS_SERVICE, "get_status_many", {"paths": ["/sa"]})
            assert raw["standby"] is True
            assert raw["leader"] == m1.client_address
        finally:
            m2.stop(), m1.stop()

    def test_standby_md_version_matches_primary(self, tmp_path):
        """The invalidation log is journal-driven, so a caught-up
        standby counts the EXACT version sequence the primary stamps —
        the coherence contract standby reads ride on (docs/ha.md)."""
        m1, m2 = start_primary_standby(tmp_path)
        try:
            c = FsMasterClient(m1.address)
            for i in range(7):
                c.create_directory(f"/v{i}")
            c.rename("/v0", "/v0r")
            c.delete("/v1")
            want = m1.fs_master.invalidations.version
            assert want > 0
            wait_until(
                lambda: m2.fs_master.invalidations.version == want,
                msg="standby invalidation version catch-up")
        finally:
            m2.stop(), m1.stop()

    def test_client_redirects_write_and_routes_reads(self, tmp_path):
        from alluxio_tpu.metrics import metrics

        m1, m2 = start_primary_standby(tmp_path)
        try:
            standby = f"localhost:{m2.standby_rpc_port}"
            redirects = metrics().counter("Client.FailoverRedirects")
            standby_reads = metrics().counter("Client.StandbyReads")
            r0, s0 = redirects.count, standby_reads.count
            # standby FIRST in the list: the write must redirect to the
            # leader via the hint without surfacing an error
            c = FsMasterClient(f"{standby},{m1.address}",
                               retry_duration_s=15.0, fastpath=False,
                               standby_reads=True)
            c.create_directory("/via-redirect")
            assert redirects.count > r0
            wait_until(lambda: m2.fs_master.exists("/via-redirect"),
                       msg="standby tail")
            for _ in range(4):
                assert c.exists("/via-redirect")
            assert standby_reads.count > s0
        finally:
            m2.stop(), m1.stop()


# -------------------------------------------------------------- quorum view
class TestMastersView:
    def test_get_masters_and_fsadmin_report(self, tmp_path):
        from alluxio_tpu.shell.command import ShellContext
        from alluxio_tpu.shell.fsadmin_shell import ADMIN_SHELL

        m1, m2 = start_primary_standby(tmp_path)
        try:
            # both masters publish; the registry is the shared view
            wait_until(lambda: len(MasterRegistry(
                str(tmp_path / "journal")).list()) == 2,
                msg="registry rows")
            rep = MetaMasterClient(m1.address).get_masters()
            roles = {r["address"]: r["role"] for r in rep["masters"]}
            assert roles[m1.client_address] == "PRIMARY"
            assert roles[m2.client_address] == "STANDBY"
            assert rep["leader"] == m1.client_address
            # the standby serves the same view (read-marked RPC)
            rep2 = MetaMasterClient(
                f"localhost:{m2.standby_rpc_port}",
                fastpath=False).get_masters()
            assert {r["address"] for r in rep2["masters"]} == set(roles)
            # fsadmin report masters renders it, exit 0 with a primary
            conf = make_conf(tmp_path)
            conf.set(Keys.MASTER_HOSTNAME, "localhost")
            conf.set(Keys.MASTER_RPC_PORT, m1.rpc_port)
            out, err = io.StringIO(), io.StringIO()
            code = ADMIN_SHELL.run(["report", "masters"],
                                   ShellContext(conf, out=out, err=err))
            text = out.getvalue()
            assert code == 0
            assert "PRIMARY" in text and "STANDBY" in text
            assert m1.client_address in text
        finally:
            m2.stop(), m1.stop()

    def test_quorum_degraded_rule_fires_on_missing_member(self):
        from alluxio_tpu.master.health import quorum_degraded_rule

        class _Ctx:
            def __init__(self, live, expected):
                self._v = {"Master.HaQuorumLive": live,
                           "Master.HaQuorumExpected": expected}

            def window_mean(self, name, source, window_s):
                return self._v.get(name)

        rule = quorum_degraded_rule(3)
        assert rule.needs_history
        assert rule.probe(_Ctx(3.0, 3.0)) == []
        v = rule.probe(_Ctx(2.0, 3.0))
        assert len(v) == 1 and "2.0 of 3" in v[0].summary
        # a single blip inside the mean window stays quiet
        assert rule.probe(_Ctx(2.8, 3.0)) == []


# ----------------------------------------------------- location drift push
class TestLocationDriftInvalidation:
    def test_quarantine_invalidates_cached_paths(self, tmp_path):
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        with LocalCluster(str(tmp_path), num_workers=1) as cluster:
            fs = cluster.file_system()
            fs.write_all("/drift/a.bin", b"x" * 4096)
            master = cluster.master
            inval = master.fs_master.invalidations
            v0 = inval.version
            wid = cluster.workers[0].worker.worker_id
            assert master.block_master.quarantine_worker(wid)
            batch = inval.since(v0)
            assert "/drift/a.bin" in batch["prefixes"], \
                "quarantine did not push the path into the " \
                "invalidation log"
            v1 = inval.version
            assert master.block_master.release_worker(wid)
            assert "/drift/a.bin" in inval.since(v1)["prefixes"]

    def test_mass_drift_collapses_to_root_invalidation(self, tmp_path):
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        with LocalCluster(str(tmp_path), num_workers=1) as cluster:
            master = cluster.master
            inval = master.fs_master.invalidations
            v0 = inval.version
            master.block_master._notify_location_change(
                list(range(5000)))
            batch = inval.since(v0)
            assert batch["prefixes"] == ["/"], \
                "mass drift should invalidate the root, not flood " \
                "the ring"

    def test_free_pushes_invalidation(self, tmp_path):
        """free() evicts replicas under untouched inodes — no other
        journal entry would repair a cached status, so it journals its
        own INVALIDATE_PATH for the freed subtree."""
        from alluxio_tpu.client.streams import WriteType
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        with LocalCluster(str(tmp_path), num_workers=1) as cluster:
            fs = cluster.file_system()
            fs.write_all("/freed/a.bin", b"x" * 4096,
                         write_type=WriteType.CACHE_THROUGH)
            master = cluster.master
            inval = master.fs_master.invalidations
            v0 = inval.version
            assert master.fs_master.free("/freed", recursive=True)
            assert "/freed" in inval.since(v0)["prefixes"], \
                "free() did not push an invalidation for the freed " \
                "subtree"

    def test_recursive_delete_one_prefix_invalidation(self, tmp_path):
        """A recursive delete invalidates ONE subtree prefix (the
        root's entry; descendants are journaled "covered") — per-victim
        ring entries would push a big delete past the bounded ring's
        horizon and reset every client cache."""
        from alluxio_tpu.minicluster.local_cluster import LocalCluster

        with LocalCluster(str(tmp_path), num_workers=0) as cluster:
            fs = cluster.file_system()
            for i in range(30):
                fs.create_directory(f"/big/sub{i}")
            master = cluster.master
            inval = master.fs_master.invalidations
            v0 = inval.version
            fs.delete("/big", recursive=True)
            batch = inval.since(v0)
            assert "/big" in batch["prefixes"]
            assert not any(p.startswith("/big/")
                           for p in batch["prefixes"]), batch
            assert inval.version - v0 <= 2, \
                "recursive delete flooded the invalidation ring"

    def test_standby_redirects_ufs_metadata_load(self, tmp_path):
        """A standby read of a UFS path not yet loaded into the
        namespace needs to JOURNAL the load — only the primary can;
        the standby must answer with a NotPrimaryError redirect, not a
        JournalClosedError."""
        import os as _os

        m1, m2 = start_primary_standby(tmp_path)
        try:
            # the torn-read exclusion must be wired on the standby
            assert m2._tailer._apply_exclusion is not None
            FsMasterClient(m1.address).create_directory("/warm")
            standby = f"localhost:{m2.standby_rpc_port}"
            sc = FsMasterClient(standby, retry_duration_s=10.0,
                                fastpath=False)
            wait_until(lambda: sc.exists("/warm"), msg="standby tail")
            # drop a file straight into the root UFS — present in the
            # UFS, absent from the namespace, so get_status must load
            ufs_root = str(tmp_path / "underFSStorage")
            _os.makedirs(ufs_root, exist_ok=True)
            with open(_os.path.join(ufs_root, "ufs-only.bin"), "wb") as f:
                f.write(b"u" * 128)
            # a fresh standby has no live UFS instances (fs_master.start
            # wires them at promotion); a deposed-then-demoted master
            # keeps them — simulate that lifecycle, the case where the
            # load path actually runs on a tail-only journal
            for info in m2.fs_master.mount_table.mount_points():
                if not m2.fs_master._ufs.has(info.mount_id):
                    m2.fs_master._ufs.add_mount(
                        info.mount_id, info.ufs_uri, info.properties)
            with pytest.raises(NotPrimaryError) as ei:
                RpcChannel(standby).call(FS_SERVICE, "get_status",
                                         {"path": "/ufs-only.bin"})
            assert ei.value.leader == m1.client_address
            # in a batched call the load fails the CALL, not the path:
            # the error speaks of this master, and the primary can answer
            with pytest.raises(NotPrimaryError) as ei:
                RpcChannel(standby).call(
                    FS_SERVICE, "get_status_many",
                    {"paths": ["/warm", "/ufs-only.bin"]})
            assert ei.value.leader == m1.client_address
        finally:
            m2.stop(), m1.stop()

    def test_md_version_survives_checkpoint_bootstrap(self, tmp_path):
        """A master bootstrapping from a checkpoint never re-applies
        the entries the checkpoint covers, so the checkpoint itself
        carries the invalidation version those entries advanced — the
        restarted master stamps the same md_version sequence a full
        replay would (the standby read-coherence contract rides on
        this)."""
        m1 = FaultTolerantMasterProcess(make_conf(tmp_path))
        m1.start()
        try:
            c = FsMasterClient(m1.address)
            for i in range(5):
                c.create_directory(f"/ck{i}")
            m1.journal.checkpoint()
            want = m1.fs_master.invalidations.version
            assert want > 0
        finally:
            m1.stop()
        m2 = FaultTolerantMasterProcess(make_conf(tmp_path))
        m2.start()
        try:
            assert m2.serving
            assert m2.fs_master.invalidations.version == want, \
                "checkpoint bootstrap restarted the md_version count"
        finally:
            m2.stop()


# -------------------------------------------------------------- crash points
class TestCrashPoints:
    def test_fsync_failure_latches_journal_broken(self, tmp_path):
        """The ack-durability crash point: an injected fsync failure
        must fail the WRITE (never ack-then-lose) and latch the journal
        broken; replay after restart sees only acked entries."""
        from alluxio_tpu.journal.system import LocalJournalSystem

        class _Rec:
            journal_name = "Recorder"

            def __init__(self):
                self.values = []

            def process_entry(self, e):
                if e.type == "inode_file":
                    self.values.append(e.payload.get("v"))
                    return True
                return False

            def snapshot(self):
                return {"values": list(self.values)}

            def restore(self, snap):
                self.values = list(snap.get("values", []))

            def reset_state(self):
                self.values = []

        folder = str(tmp_path / "j")
        j = LocalJournalSystem(folder)
        rec = _Rec()
        j.register(rec)
        j.start()
        j.gain_primacy()
        j.start_group_commit(0.0)
        with j.create_context() as ctx:
            ctx.append("inode_file", {"v": 1})  # acked + durable
        try:
            faults.injector().set(fsync_errors=1)
            with pytest.raises(JournalClosedError):
                with j.create_context() as ctx:
                    ctx.append("inode_file", {"v": 2})  # fsync dies
            # latched: later writes fail too, no silent limping
            with pytest.raises(JournalClosedError):
                with j.create_context() as ctx:
                    ctx.append("inode_file", {"v": 3})
        finally:
            faults.injector().reset()
        j.stop()
        j2 = LocalJournalSystem(folder)
        rec2 = _Rec()
        j2.register(rec2)
        j2.start()
        j2.gain_primacy()
        assert 1 in rec2.values, "ACKED entry lost across restart"
        assert 3 not in rec2.values, "failed write leaked an ack"
        j2.stop()

    def test_deposed_leader_writes_fenced_under_partition(self, tmp_path):
        """Partition the raft leader away from its quorum: its writes
        must fail (no ack without quorum), it must step down, and after
        healing it rejoins as a follower of the new leader."""
        from alluxio_tpu.journal.raft import EmbeddedJournalSystem
        from alluxio_tpu.minicluster.ha_cluster import free_ports

        ports = free_ports(3)
        addrs = ",".join(f"127.0.0.1:{p}" for p in ports)
        systems = []
        for i, p in enumerate(ports):
            j = EmbeddedJournalSystem(
                str(tmp_path / f"m{i}"), address=f"127.0.0.1:{p}",
                addresses=addrs, election_timeout_ms=(300, 600),
                heartbeat_interval_ms=50)
            j.register(_KvComponent())
            systems.append(j)
        try:
            for j in systems:
                j.start()
            wait_until(lambda: any(j.node.leader_ready()
                                   for j in systems), timeout=30,
                       msg="initial election")
            leader = next(j for j in systems if j.node.leader_ready())
            with leader.create_context() as ctx:
                ctx.append("kv_put", {"k": "a", "v": 1})
            faults.injector().set(partitioned=[leader.node.node_id])
            # the fenced leader's writes fail typed — never ambiguous acks
            with pytest.raises(JournalClosedError):
                with leader.create_context() as ctx:
                    ctx.append("kv_put", {"k": "b", "v": 2})
            wait_until(lambda: any(
                j is not leader and j.node.leader_ready()
                for j in systems), timeout=30, msg="new leader")
            survivor = next(j for j in systems
                            if j is not leader and j.node.leader_ready())
            with survivor.create_context() as ctx:
                ctx.append("kv_put", {"k": "c", "v": 3})
            faults.injector().set(partitioned=[])
            wait_until(lambda: not leader.node.is_leader(), timeout=30,
                       msg="old leader steps down")
            wait_until(lambda: leader.sequence == survivor.sequence,
                       timeout=30, msg="old leader catches up")
        finally:
            faults.injector().reset()
            for j in systems:
                j.stop()


class _KvComponent:
    journal_name = "Kv"

    def __init__(self):
        self.data = {}

    def process_entry(self, e):
        if e.type == "kv_put":
            self.data[e.payload["k"]] = e.payload["v"]
            return True
        return False

    def snapshot(self):
        return {"data": dict(self.data)}

    def restore(self, snap):
        self.data = dict(snap.get("data", {}))

    def reset_state(self):
        self.data = {}


# ------------------------------------------------------------- chaos drill
@pytest.mark.slow
class TestChaosDrill:
    def test_scheduled_chaos_preserves_invariants(self, tmp_path):
        """The headline drill: under live read/write load, a scheduled
        fault plan (kill primary -> freeze a standby tailer -> restart
        the dead master -> partition a member -> heal) must lose zero
        acknowledged writes, surface zero errors for idempotent ops,
        and never serve a standby read staler than its advertised
        md_version."""
        import threading

        from alluxio_tpu.minicluster.ha_cluster import (
            HaCluster, WriteLedger,
        )

        cluster = HaCluster(str(tmp_path), num_masters=3, num_workers=0)
        try:
            cluster.start()
            writer = cluster.fs_client(retry_duration_s=90.0,
                                       fastpath=False)
            reader = cluster.fs_client(retry_duration_s=90.0,
                                       fastpath=False)
            writer.create_directory("/chaos")
            ledger = WriteLedger()
            stop = threading.Event()
            errors = []
            staleness = []

            def write_loop():
                i = 0
                while not stop.is_set():
                    path = f"/chaos/w{i:05d}"
                    try:
                        writer.create_directory(path)
                        _, stamp = reader.get_status(
                            path, want_version=True)
                        ledger.record(path, stamp)
                    except Exception as e:  # noqa: BLE001 - the invariant
                        errors.append(e)
                        return
                    i += 1
                    time.sleep(0.02)

            probe_clients = {}  # port -> client, reused across ticks

            def probe_loop():
                while not stop.is_set():
                    port = None
                    for i in cluster.standby_indices():
                        m = cluster.masters[i]
                        if m is not None and m.standby_rpc_port:
                            port = m.standby_rpc_port
                            break
                    if port is None:
                        time.sleep(0.1)
                        continue
                    sc = probe_clients.get(port)
                    if sc is None:
                        sc = probe_clients[port] = FsMasterClient(
                            f"localhost:{port}", retry_duration_s=1.0,
                            fastpath=False)
                    try:
                        infos, stamp = sc.list_status(
                            "/chaos", want_version=True)
                    except Exception:  # noqa: BLE001 standby mid-churn
                        time.sleep(0.1)
                        continue
                    names = {"/chaos/" + x.name for x in infos}
                    staleness.extend(
                        ledger.staleness_violations(names, stamp))
                    time.sleep(0.05)

            wt = threading.Thread(target=write_loop, daemon=True)
            pt = threading.Thread(target=probe_loop, daemon=True)
            wt.start(), pt.start()
            plan = FaultPlan([
                FaultStep(1.0, "kill_primary"),
                FaultStep(4.0, "freeze_tailer", index=0),
                FaultStep(6.0, "unfreeze_tailer"),
                FaultStep(6.5, "restart_master", index=0),
                FaultStep(9.0, "partition", index=0),
                FaultStep(11.0, "heal_partition"),
            ])
            actions = dict(cluster.chaos_actions())
            # the plan names indices relative to live members: step 2
            # freezes whichever standby exists then — resolve lazily
            actions["freeze_tailer"] = lambda index: \
                cluster.freeze_tailer(cluster.standby_indices()[0])
            actions["restart_master"] = lambda index: \
                cluster.restart_master(
                    next(i for i, m in enumerate(cluster.masters)
                         if m is None))
            actions["partition"] = lambda index: \
                cluster.partition(cluster.standby_indices()[0])
            log = plan.run(actions)
            assert all(e["ok"] for e in log), log
            time.sleep(2.0)
            stop.set()
            wt.join(timeout=15), pt.join(timeout=15)
            assert not errors, \
                f"idempotent write surfaced an error: {errors[0]!r}"
            assert len(ledger.entries) > 20, \
                "drill produced too little load to mean anything"
            missing = ledger.verify_durable(
                cluster.fs_client(retry_duration_s=60.0,
                                  fastpath=False))
            assert not missing, f"ACKED writes lost: {missing[:5]}"
            assert not staleness, \
                f"standby reads staler than advertised: {staleness[:5]}"
        finally:
            cluster.stop()
