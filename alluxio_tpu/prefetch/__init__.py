"""Clairvoyant prefetch service: epoch-aware block scheduling into tiers.

With a seeded shuffle the exact per-epoch access order is known before
the first step runs (NoPFS, arxiv 2101.08734; Hoard, arxiv 1812.00669),
so the data plane can plan — not guess — which blocks must already be
resident in which tier when the consumer arrives:

- :mod:`~alluxio_tpu.prefetch.oracle` derives the exact future access
  sequence from (manifest, seed, epoch, cursor);
- :mod:`~alluxio_tpu.prefetch.scheduler` turns the lookahead window into
  tier-placement plans (HBM vs DRAM vs skip) under a byte budget, with
  deadline/lateness tracking and backpressure;
- :mod:`~alluxio_tpu.prefetch.agent` executes plans each heartbeat:
  async worker-tier loads + eviction pins, and HBM adoption through the
  consumer's :class:`~alluxio_tpu.client.jax_io.DeviceBlockLoader`;
- :mod:`~alluxio_tpu.prefetch.service` assembles the control loop from
  configuration and binds it to a loader.

Where the data set is already whole in a same-host worker's MEM tier
(the benchmark's ``shuffled-32m``), set ``atpu.prefetch.hbm.fraction``
to 1.0: a DRAM placement there is a ``get_block_info`` and a
``prefetch_pin`` RPC that stage nothing, and only an HBM placement
moves bytes. On the chip (PERF.md section 6, PR 35) the service's ORDER
is what such a job needs; its placements won and cost nothing at any
budget tried, because the plan starts at the cursor, where the loader's
producer already is (docs/prefetch.md, "A same-host warm worker").
"""

from alluxio_tpu.prefetch.oracle import (  # noqa: F401
    AccessOracle, BlockRef, DatasetManifest,
)
from alluxio_tpu.prefetch.scheduler import (  # noqa: F401
    PlacementAction, PrefetchScheduler, TIER_DRAM, TIER_HBM,
)
from alluxio_tpu.prefetch.agent import (  # noqa: F401
    JobServiceExecutor, PrefetchAgent, WorkerTierExecutor,
)
from alluxio_tpu.prefetch.service import PrefetchService  # noqa: F401
