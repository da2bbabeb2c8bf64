"""Same-host zero-copy data plane: the SHM lease protocol.

The worker's MEM tier lives on ``/dev/shm`` (``atpu.worker.shm.dir``) —
a committed top-tier block file *is* a named shared-memory segment, and a
lower tier's is an ordinary file that ``mmap`` takes the same way. This
package holds the protocol both sides of the zero-copy path speak, for a
block in any tier:

- the **worker** (``worker/shm_store.py``) grants a co-located client a
  *lease* on a segment: ``shm_open`` returns the file path + a lease id,
  and pins the block in :class:`TieredBlockStore` so eviction cannot
  demote or unlink it while mapped. Leases are **TTL-bounded, not
  session-bound**: a SIGKILLed client's pins self-expire one TTL later
  (the crash-safe reclamation path — same shape as prefetch pins), while
  live clients renew lazily via ``shm_renew``.
- the **client** (``client/shm_transport.py``) mmaps the segment once
  and serves every subsequent read of the block as a ``memoryview``
  slice — no RPC, no serialization, no copy; ``np.frombuffer`` over the
  same pages feeds ``jax.device_put`` directly, so a same-host read
  costs exactly one host->device transfer.

Fallback contract: every failure in this plane (lease denied, segment
unavailable, worker restarted and forgot the lease, mmap error) is a
typed, *retryable-elsewhere* signal — the routing layer in
``client/remote_read.py`` / ``client/block_streams.py`` catches it and
transparently re-issues the read on the remote gRPC path. The SHM plane
can only ever make reads faster, never fail them.

Protocol summary (docs/small_reads.md has the full matrix):

======================  ================================================
RPC                     semantics
======================  ================================================
``shm_open``            grant lease: {lease_id, path, length, ttl_s};
                        raises ShmLeaseDeniedError (table full / fault)
                        or ShmSegmentUnavailableError (not cached on
                        this worker)
``shm_renew``           extend lease TTL; {ok: False} for an unknown
                        lease (worker restarted) — client re-opens
``shm_release``         drop lease; last lease on a block unpins it
======================  ================================================
"""

from __future__ import annotations

from alluxio_tpu.utils.exceptions import (
    AlluxioTpuError, register_wire_error,
)


@register_wire_error
class ShmLeaseDeniedError(AlluxioTpuError):
    """Worker declined to grant/keep an SHM lease (lease table at
    ``atpu.worker.shm.max.leases``, or an injected
    ``atpu.debug.fault.shm.lease.deny.rate`` fault). The client falls
    back to the remote read path; retry-later is implied, not required."""

    code = "RESOURCE_EXHAUSTED"


@register_wire_error
class ShmSegmentUnavailableError(AlluxioTpuError):
    """The block has no mappable file on this worker (not cached, or
    evicted during the grant), or the client's own mapping of it was
    released. Not an error for the read itself — the remote path serves
    it, or the block is opened again."""

    code = "NOT_FOUND"
