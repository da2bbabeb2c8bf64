"""Subprocess body for the multi-host ICI data-plane test: one JAX
process of a 2-process x 4-device CPU "slice", driving MeshBlockCache
against a live cluster across process boundaries.

argv: <process_id> <coordinator_port> <master_addr> <paths comma-sep>
      <block_bytes>

Prints ``MH-OK <json>`` on success; any exception exits non-zero.
"""

import json
import os
import sys


def main() -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    inherited = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
    os.environ["XLA_FLAGS"] = " ".join(
        ["--xla_force_host_platform_device_count=4"] + inherited)

    pid = int(sys.argv[1])
    coord_port = int(sys.argv[2])
    master_addr = sys.argv[3]
    paths = sys.argv[4].split(",")
    block_bytes = int(sys.argv[5])

    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{coord_port}",
        num_processes=2, process_id=pid)
    assert jax.device_count() == 8, jax.device_count()
    assert len(jax.local_devices()) == 4

    import numpy as np
    from jax.sharding import Mesh

    from alluxio_tpu.client.file_system import FileSystem
    from alluxio_tpu.conf import Configuration
    from alluxio_tpu.parallel.ici_store import MeshBlockCache

    mesh = Mesh(np.array(jax.devices()), ("data",))
    fs = FileSystem(master_addr, conf=Configuration(load_env=False))
    cache = MeshBlockCache(mesh, axis="data", block_bytes=block_bytes,
                           client_host=f"mh-proc{pid}")

    # 1) cross-process warm-set assembly: each process loads only its
    #    addressable devices' shards; make_array_from_single_device_arrays
    #    builds the global array (exactly where multi-host bites)
    cached = cache.load_global(fs, paths)
    assert cached.shape[0] == 8 and not cached.is_fully_addressable

    import jax.numpy as jnp

    # 2) a global collective over the sharded warm set
    total = int(jax.jit(
        lambda x: x.astype(jnp.int64).sum())(cached))

    # 3) O(batch) cross-host assembly by global index
    batch = cache.global_batch(cached, [0, 3, 5])
    batch_np = np.asarray(batch.addressable_shards[0].data)
    row_sums = [int(r) for r in
                batch_np.astype(np.int64).sum(axis=1)]

    # 4) replicate a single hot block to every device
    rep = cache.replicate(cached, 6)
    rep_host = np.asarray(rep.addressable_shards[0].data)
    rep_sum = int(rep_host.astype(np.int64).sum())
    assert all(np.array_equal(
        rep_host, np.asarray(s.data)) for s in rep.addressable_shards)

    fs.close()
    print("MH-OK " + json.dumps({
        "pid": pid, "total": total, "rows": row_sums,
        "rep_sum": rep_sum,
        "n_addressable": len(cached.addressable_shards)}), flush=True)


if __name__ == "__main__":
    main()
