"""ICI data plane: warm cached blocks sharded across the device mesh,
served to peers by XLA collectives instead of per-block gRPC.

**The TPU-native transport the reference has no analogue for** (SURVEY
§5.8: "NEW: ICI collectives as the intra-slice 'remote read'"; §2.11
block-striping row: "block map keyed by device mesh position"). In the
reference, a client reading a block cached on another worker opens a gRPC
stream through both hosts' NICs
(``client/block/stream/GrpcDataReader.java:49``). On a TPU slice the warm
copy already sits in a peer chip's HBM one ICI hop away — so the "remote
read" becomes an ``all_gather``/``ppermute`` *inside jit*, riding ICI at
hundreds of GB/s with zero host traffic, zero gRPC, and zero
host<->device copies.

Design:

- ``MeshBlockCache.load_global`` builds ONE global ``jax.Array`` of shape
  ``(n_blocks, block_bytes)`` sharded ``P(axis)`` over the mesh: device
  ``d`` holds blocks ``[d*per_dev, (d+1)*per_dev)`` in its HBM. Placement
  IS the mesh position — the client-side block map for the warm set.
  Each host loads only ITS devices' blocks from the co-located worker
  (same-host lease plane, an mmap); assembly uses
  ``jax.make_array_from_single_device_arrays`` — the idiomatic multi-host
  pattern (no host ever materializes the global array).
- Warm "remote reads" are jitted collectives over the cached array:
  ``gather_all`` (every device sees every block; ICI all-gather),
  ``ring_shift`` (each device reads its neighbor's shard; ICI ppermute —
  the sequence-parallel access pattern), and ``global_batch`` (assemble a
  batch from blocks wherever they live, fused into the consumer's jit:
  every device copies the rows it owns into a zero-padded (batch, elems)
  buffer, one Pallas kernel of row copies with the ownership mask folded
  into the fetch, ``ops/row_copy_kernel.py``, and ONE ``psum`` merges
  the buffers; no gather, no all-gather).
- ``replicate`` broadcasts a hot shard to every device
  (``device_put_replicated`` fan-out; reference analogue:
  ``ReplicationChecker`` + ``job/plan/replicate`` — but one collective,
  not N gRPC streams).

Cold loads still ride the worker data plane (UFS -> worker tier -> host
-> HBM); this module is the warm path on top of it.
"""

from __future__ import annotations

import contextlib
import contextvars
import time
import uuid
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from alluxio_tpu.metrics import metrics
from alluxio_tpu.parallel.mesh import DATA_AXIS, named_sharding
from alluxio_tpu.utils.tracing import tracer


@contextlib.contextmanager
def _timed(span, counter):
    """Enter ``span``; its time is also added to ``counter``, in us."""
    t0 = time.perf_counter()
    with span:
        yield
    counter.inc(int((time.perf_counter() - t0) * 1e6))


class MeshBlockCache:
    """Warm block cache sharded over a mesh axis; collective reads.

    One instance manages one dataset (an ordered list of ``(path, block)``
    pairs padded to equal block size). The global order is striped so the
    sharding is contiguous per device: global index ``g = d*per_dev + k``
    is the ``k``-th block of device ``d``.
    """

    def __init__(self, mesh, *, axis: str = DATA_AXIS,
                 block_bytes: int, dtype=np.uint8,
                 client_host: str = "") -> None:
        import socket

        import jax

        self._jax = jax
        self.mesh = mesh
        self.axis = axis
        self.block_bytes = block_bytes
        self.dtype = np.dtype(dtype)
        self.n_devices = int(np.prod([
            mesh.shape[a] for a in ([axis] if isinstance(axis, str)
                                    else axis)]))
        #: (path, block_index) in global order, set by load_global
        self.plan: List[Tuple[str, int]] = []
        #: global block index -> master block id (for placement reports)
        self.block_ids: List[int] = []
        self.client_host = client_host or socket.gethostname()
        #: this warm set's own name in the master's device block map:
        #: two caches of one host keep separate records there
        self.reporter = f"{self.client_host}/{uuid.uuid4().hex[:12]}"
        self._block_client = None
        m = metrics()
        self._blocks_loaded = m.counter("Client.JaxMeshBlocksLoaded")
        self._bytes_loaded = m.counter("Client.JaxMeshBytesLoaded")
        #: host time of a load, summed over the pool's threads
        self._host_read_us = m.counter("Client.JaxMeshHostReadUs")
        self._stack_us = m.counter("Client.JaxMeshStackUs")
        #: a shard's device_put, from its dispatch to its being ready
        self._put_us = m.counter("Client.JaxMeshPutUs")
        self._reports = m.counter("Client.JaxMeshPlacementReports")
        self._report_failures = m.counter(
            "Client.JaxMeshPlacementReportFailures")
        #: path -> master block ids (filled from loaders; avoids a
        #: get_status RPC per path on every resolve)
        self._bids_by_path: Dict[str, List[int]] = {}
        #: per_dev -> jitted batch assembler (jit caches by fn object;
        #: rebuilding the closure per call would retrace every batch)
        self._batch_fns: Dict[int, object] = {}

    # -- placement -----------------------------------------------------------
    def placement(self, n_blocks: int) -> Dict[int, int]:
        """global block index -> mesh position (the warm-set block map)."""
        per_dev = -(-n_blocks // self.n_devices)
        return {g: g // per_dev for g in range(n_blocks)}

    # -- load (cold/host path; per-host locality) ----------------------------
    def load_global(self, fs, paths: Sequence[str], *,
                    loader=None, report: bool = True,
                    io_threads: int = 8):
        """Materialize the warm set: every addressable device's shard is
        loaded from the host-local worker tier and assembled into one
        global sharded array WITHOUT any host seeing the whole dataset.
        One pool thread a mesh position of this host (so at most
        ``min(io_threads, positions)`` threads ever work: 4 of the
        default 8 on a 4-chip host) reads its shard's rows (leased mmap,
        pages made present), stacks them into ONE heap array (a copy of
        the shard beside the mapped views) and hands that to ONE
        ``device_put``, waited for in the same thread: transfers overlap
        each other and slower positions' reads, never the reads of
        their own shard. Returns with the set resident.

        ``report=True`` registers this host's device placement with the
        master block map (SURVEY §2.11 "block map keyed by device mesh
        position") so the control plane can steer consumers at warm
        copies one ICI hop away.

        ``loader``: an existing DeviceBlockLoader to reuse (a caller
        that made one over the same list); else one is built per call.
        """
        from concurrent.futures import ThreadPoolExecutor

        import jax

        from alluxio_tpu.client.jax_io import DeviceBlockLoader

        sharding = named_sharding(self.mesh, self.axis)
        own_loader = loader is None
        if own_loader:
            loader = DeviceBlockLoader(fs, paths, hbm_bytes=0,
                                       dtype=self.dtype)
        try:
            self.plan = list(loader.plan)
            self._resolve_block_ids(fs, loader)
            n = len(self.plan)
            per_dev = -(-n // self.n_devices)
            elems = self.block_bytes // self.dtype.itemsize
            # mesh-position-major device order along the sharded axis
            mesh_devs = self.mesh.devices.reshape(-1)
            addressable = {d.id for d in jax.local_devices()}
            my_positions = [p for p in range(self.n_devices)
                            if mesh_devs[p].id in addressable]

            def load_shard(d_pos: int):
                globals_ = range(d_pos * per_dev, (d_pos + 1) * per_dev)
                return self._put_rows(loader, d_pos, globals_, n, elems,
                                      mesh_devs[d_pos])

            with tracer().span(
                    "atpu.mesh.load_global", blocks=n,
                    devices=len(my_positions),
                    bytes=len(my_positions) * per_dev * self.block_bytes):
                with ThreadPoolExecutor(
                        max_workers=max(1, io_threads)) as ex:
                    # a copy of THIS context a shard: its spans are the
                    # children of load_global's in the ring
                    loading = [ex.submit(contextvars.copy_context().run,
                                         load_shard, p)
                               for p in my_positions]
                    shards = {p: f.result()
                              for p, f in zip(my_positions, loading)}
                global_shape = (per_dev * self.n_devices, elems)
                cached = jax.make_array_from_single_device_arrays(
                    global_shape, sharding,
                    [shards[p] for p in my_positions])
                if report:
                    self.report_placement(fs, my_positions, per_dev, n)
            return cached
        finally:
            if own_loader:
                loader.close()

    def _put_rows(self, loader, d_pos: int, globals_, n: int, elems: int,
                  device):
        """The rows ``globals_`` of mesh position ``d_pos`` on its
        device, ready: host rows, one stack, one ``device_put``."""
        import jax

        span = tracer().span
        real = sum(1 for g in globals_ if g < n)
        with _timed(span("atpu.mesh.read_shard", pos=d_pos, blocks=real),
                    self._host_read_us):
            rows = [self._host_row(loader, g, n, elems) for g in globals_]
        with _timed(span("atpu.mesh.stack"), self._stack_us):
            local = np.stack(rows)  # (len(globals_), elems)
        del rows
        with _timed(span("atpu.mesh.device_put", bytes=local.nbytes),
                    self._put_us):
            on_device = jax.device_put(local, device)
            on_device.block_until_ready()
        self._blocks_loaded.inc(real)
        self._bytes_loaded.inc(real * self.block_bytes)
        return on_device

    def _host_row(self, loader, g: int, n: int, elems: int):
        if g >= n:  # pad the ragged tail with zeros
            return np.zeros(elems, self.dtype)
        from alluxio_tpu import native

        # (the loader opens a row again where another pool thread's open
        # released its segment between open and view)
        host = loader.host_block(*self.plan[g])
        # one kernel call maps the block; np.stack would fault it a page
        native.prefault(host)
        if host.shape[0] != elems:
            padded = np.zeros(elems, self.dtype)
            padded[:host.shape[0]] = host
            host = padded
        return host

    def _resolve_block_ids(self, fs, loader=None) -> None:
        if loader is not None:  # loader already fetched every status
            self._bids_by_path.update(
                getattr(loader, "block_ids_by_path", {}))
        # the paths no loader resolved: one status call for the list
        todo = list(dict.fromkeys(
            p for p, _ in self.plan if p not in self._bids_by_path))
        for path, info in zip(todo, fs.get_status_many(todo)):
            self._bids_by_path[path] = list(info.block_ids)
        self.block_ids = []
        for path, idx in self.plan:
            bids = self._bids_by_path[path]
            self.block_ids.append(bids[idx] if idx < len(bids) else -1)

    # -- control-plane placement reporting -----------------------------------
    def report_placement(self, fs, my_positions: Sequence[int],
                         per_dev: int, n: int) -> None:
        """Tell the master which blocks are HBM-resident at which mesh
        position (this host's shard of the warm set only — each host
        reports its own; the master merges). A failure is counted
        (``Client.JaxMeshPlacementReportFailures``), never raised."""
        client = self._block_master_client(fs)
        if client is None:
            return
        mesh_blocks = {}
        for pos in my_positions:
            bids = [self.block_ids[g]
                    for g in range(pos * per_dev,
                                   min((pos + 1) * per_dev, n))
                    if self.block_ids[g] >= 0]
            if bids:
                mesh_blocks[pos] = bids
        with tracer().span("atpu.mesh.report_placement",
                           positions=len(mesh_blocks)):
            try:
                client.report_device_blocks(self.client_host, mesh_blocks,
                                            reporter=self.reporter)
                self._reports.inc()
            except Exception:  # noqa: BLE001 placement is advisory cache state
                self._report_failures.inc()

    def drop_placement(self, fs) -> None:
        """Warm set released: clear this warm set's device block map
        entries (pairs with eviction/close)."""
        client = self._block_master_client(fs)
        if client is not None:
            try:
                client.clear_device_blocks(self.client_host,
                                           reporter=self.reporter)
            except Exception:  # noqa: BLE001 advisory
                self._report_failures.inc()

    def _block_master_client(self, fs):
        if self._block_client is None:
            store = getattr(fs, "store", None)
            self._block_client = getattr(store, "block_master", None)
            if self._block_client is None:
                import logging

                logging.getLogger(__name__).warning(
                    "no block-master client on %r: device placement "
                    "reporting disabled", type(fs).__name__)
        return self._block_client

    # -- warm collective reads (zero host traffic) ---------------------------
    def gather_all(self, cached):
        """Every device materializes ALL blocks: one ICI all-gather inside
        jit — the collective replacement for N remote gRPC block reads.
        Returns a fn suitable for fusion into a consumer step."""
        import jax
        from jax.sharding import PartitionSpec as P

        @jax.jit
        def _gather(x):
            def f(local):  # local: (per_dev, elems)
                return jax.lax.all_gather(
                    local, self.axis, axis=0, tiled=True)

            # check_vma off: the check cannot infer the replication
            # an all_gather produces
            return jax.shard_map(
                f, mesh=self.mesh, in_specs=P(self.axis, None),
                out_specs=P(), check_vma=False)(x)

        return _gather(cached)

    def ring_shift(self, cached, shift: int = 1):
        """Each device receives its ``shift``-th neighbor's shard over the
        ICI ring (ppermute) — the sequence-parallel/ring-attention access
        pattern applied to cached data. Sharding is preserved."""
        import jax
        from jax.sharding import PartitionSpec as P

        n = self.n_devices

        @jax.jit
        def _shift(x):
            def f(local):
                # (source, dest): device d receives from (d + shift) % n
                perm = [((d + shift) % n, d) for d in range(n)]
                return jax.lax.ppermute(local, self.axis, perm)

            return jax.shard_map(
                f, mesh=self.mesh, in_specs=P(self.axis, None),
                out_specs=P(self.axis, None), check_vma=False)(x)

        return _shift(cached)

    def global_batch(self, cached, indices):
        """Assemble a batch of blocks by GLOBAL index regardless of which
        device caches them, moving O(batch) bytes over ICI — NOT the
        whole warm set. Each device COPIES the requested rows it owns
        out of its local shard into a zero-padded (batch, elems) buffer
        (``ops/row_copy_kernel.masked_rows``: row copies with the
        ownership mask folded in, no gather; rows of other owners stay
        zero and are never read), then ONE psum merges the batch: ICI
        traffic is the reduction of a (batch, elems) buffer, independent
        of warm-set size. ``indices``: 1-D array of global block ids; an
        index no device owns, negative or past the last padded row, is
        a row of zeros. Output is replicated (every device gets the
        whole batch); compose into the consumer's jit so XLA overlaps
        the collective with compute."""
        import jax.numpy as jnp

        per_dev = cached.shape[0] // self.n_devices
        return self.batch_fn(per_dev)(cached, jnp.asarray(indices))

    def batch_fn(self, per_dev: int):
        """The jitted O(batch) assembler, cached per ``per_dev`` (exposed
        so consumers can fuse it into their step and tests can inspect
        the lowering): ``masked_rows`` on every device's shard (Mosaic
        on TPUs, the Pallas interpreter on any other mesh), then one
        ``psum`` over the mesh axis. The cache's elements are 1, 2 or 4
        bytes wide."""
        cached_fn = self._batch_fns.get(per_dev)
        if cached_fn is not None:
            return cached_fn
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from alluxio_tpu.ops.row_copy_kernel import masked_rows

        interpret = self.mesh.devices.flat[0].platform != "tpu"

        @jax.jit
        def _assemble(x, idx):
            def f(local, idx_rep):
                # local: (per_dev, elems); idx_rep: (B,) global indices
                pos = jax.lax.axis_index(self.axis)
                local_idx = idx_rep - pos * per_dev
                mine = (local_idx >= 0) & (local_idx < per_dev)
                rows = masked_rows(
                    local, jnp.clip(local_idx, 0, per_dev - 1), mine,
                    interpret=interpret)          # (B, elems)
                # O(batch) collective: merge owners' contributions
                return jax.lax.psum(rows, self.axis)

            return jax.shard_map(
                f, mesh=self.mesh, in_specs=(P(self.axis, None), P()),
                out_specs=P(), check_vma=False)(x, idx)

        self._batch_fns[per_dev] = _assemble
        return _assemble

    def replicate(self, cached, block_index: int):
        """Fan a hot block out to EVERY device (the
        ``device_put_replicated``/ICI-broadcast replication of SURVEY
        §2.11): one collective broadcast, not N point-to-point streams.
        Returns a fully-replicated (elems,) array."""
        import jax
        import jax.numpy as jnp

        out_sharding = named_sharding(self.mesh)  # replicated

        @jax.jit
        def _pick(x):
            row = jax.lax.dynamic_slice_in_dim(x, block_index, 1, axis=0)
            return jax.lax.with_sharding_constraint(
                jnp.squeeze(row, axis=0), out_sharding)

        return _pick(cached)

    # -- warm-set turnover (eviction/refresh) --------------------------------
    def turnover(self, cached, fs, replacements: Dict[int, Tuple[str, int]],
                 *, loader=None, report: bool = True):
        """Replace warm-set rows in place: ``replacements`` maps a global
        block index -> a new ``(path, block_index)`` source. Only hosts
        owning a replaced row do IO, and each touched device gets ONE
        donated in-place row update — O(changed blocks) host->device
        traffic, untouched shards are reused as-is. The refreshed
        placement is re-reported to the master block map.

        This is the warm-set eviction/refresh story: evict = replace a
        cold block with the next epoch's data; the HBM footprint never
        grows (the old shard buffer is donated into the update).
        """
        import jax

        from alluxio_tpu.client.jax_io import DeviceBlockLoader

        if not replacements:
            return cached
        n = len(self.plan)
        per_dev = cached.shape[0] // self.n_devices
        elems = cached.shape[1]
        sharding = named_sharding(self.mesh, self.axis)
        mesh_devs = self.mesh.devices.reshape(-1)
        addressable = {d.id for d in jax.local_devices()}
        my_positions = [p for p in range(self.n_devices)
                        if mesh_devs[p].id in addressable]
        # validate EVERY index before mutating the plan: a bad key must
        # not leave plan/device state describing different data
        for g in replacements:
            if not 0 <= g < n:
                raise IndexError(f"global block index {g} out of range")
        for g, src in replacements.items():
            self.plan[g] = tuple(src)
        self._resolve_block_ids(fs)

        new_paths = sorted({p for p, _i in replacements.values()})
        own_loader = loader is None
        if own_loader:
            loader = DeviceBlockLoader(fs, new_paths, hbm_bytes=0,
                                       dtype=self.dtype)
        try:
            @partial(jax.jit, donate_argnums=0)
            def _update(local, rows, data):
                return local.at[rows].set(data)

            shards = {s.device: s.data for s in cached.addressable_shards}
            for pos in my_positions:
                dev = mesh_devs[pos]
                touched = sorted(g for g in replacements
                                 if g // per_dev == pos)
                if not touched:
                    continue
                data = self._put_rows(loader, pos, touched, n, elems, dev)
                rows = np.asarray([g - pos * per_dev for g in touched])
                shards[dev] = _update(shards[dev],
                                      jax.device_put(rows, dev), data)
            cached = jax.make_array_from_single_device_arrays(
                (per_dev * self.n_devices, elems), sharding,
                [shards[mesh_devs[p]] for p in my_positions])
            if report:
                self.report_placement(fs, my_positions, per_dev, n)
            return cached
        finally:
            if own_loader:
                loader.close()

    # -- introspection -------------------------------------------------------
    def describe_placement(self, cached) -> Dict[int, List[int]]:
        """mesh position -> global block ids resident there (from the
        REAL sharding of the cached array, not the nominal plan)."""
        out: Dict[int, List[int]] = {}
        per_dev = cached.shape[0] // self.n_devices
        mesh_devs = list(self.mesh.devices.reshape(-1))
        for shard in cached.addressable_shards:
            pos = mesh_devs.index(shard.device)
            start = shard.index[0].start or 0
            out[pos] = list(range(start, start + per_dev))
        return out
