"""Access oracle: the exact future block-access order from a seeded shuffle.

Clairvoyance, not prediction (NoPFS, arxiv 2101.08734): a training run
that shuffles with a known seed visits blocks in a sequence that is a
pure function of ``(manifest, seed, epoch)``. The oracle materializes
that sequence per host shard and answers "what are the next *k*
accesses after cursor position *p*" — including across the epoch
boundary, so the tail of epoch *e* already prefetches the head of
epoch *e+1*.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np

from alluxio_tpu.client.file_system import STATUS_BATCH_PATHS
from alluxio_tpu.utils.tracing import tracer

#: epoch sequences kept hot: the live epoch plus a lookahead window
#: several epochs deep (planner) plus the previous epoch (stragglers)
_EPOCH_CACHE_SIZE = 12

#: ``next_use`` of a block this host does not read within the epochs
#: the oracle keeps: later than every access it can name
NEVER = 1 << 62


@dataclass(frozen=True)
class BlockRef:
    """One block of the dataset, with everything an executor needs to
    make it resident (UFS source for cold loads, identity for pins)."""

    path: str
    block_index: int
    block_id: int
    length: int
    offset: int = 0
    file_id: int = 0
    ufs_path: str = ""
    mount_id: int = 0
    persisted: bool = False


@dataclass(frozen=True)
class DatasetManifest:
    """Immutable block-level view of the dataset, in file order."""

    blocks: Tuple[BlockRef, ...] = field(default_factory=tuple)
    #: the resolved (path, FileInfo) pairs behind ``blocks`` — kept so
    #: a consumer wiring a loader to the same paths reuses them
    #: instead of paying a second get_status round per file
    file_infos: Tuple = field(default_factory=tuple)

    @classmethod
    def from_fs(cls, fs, paths: Sequence[str]) -> "DatasetManifest":
        """Resolve paths through the metadata master into block refs
        (block ids, per-block lengths, and the UFS coordinates the
        async-cache path needs for cold loads). ONE status call for the
        list: a ``FileInfo`` carries its block ids, length and block
        size, and a block's offset and length follow from those, so a
        job's start makes no call a file."""
        blocks: List[BlockRef] = []
        file_infos: List[tuple] = []
        with tracer().span("atpu.prefetch.manifest", files=len(paths),
                           calls=-(-len(paths) // STATUS_BATCH_PATHS)
                           ) as sp:
            for path, info in zip(paths, fs.get_status_many(paths)):
                file_infos.append((str(path), info))
                bs = info.block_size_bytes
                for i, block_id in enumerate(info.block_ids):
                    blocks.append(BlockRef(
                        path=info.path, block_index=i, block_id=block_id,
                        length=max(0, min(bs, info.length - i * bs)),
                        offset=i * bs, file_id=info.file_id,
                        ufs_path=info.ufs_path, mount_id=info.mount_id,
                        persisted=info.persisted))
            if sp is not None:
                sp.tags["blocks"] = len(blocks)
        return cls(blocks=tuple(blocks), file_infos=tuple(file_infos))

    @property
    def total_bytes(self) -> int:
        return sum(b.length for b in self.blocks)

    def __len__(self) -> int:
        return len(self.blocks)

    def __iter__(self) -> Iterator[BlockRef]:
        return iter(self.blocks)


class AccessOracle:
    """Per-host exact access sequences for every epoch.

    The permutation for epoch *e* is drawn from
    ``np.random.SeedSequence([seed, e])`` — independent of process,
    cursor state, or call order, so every host (and the scheduler, and a
    re-started agent) derives the identical sequence. Hosts consume
    disjoint strided shards of the global permutation (host *h* of *H*
    takes positions ``h, h+H, h+2H, ...``), mirroring per-host sharded
    loading.
    """

    def __init__(self, manifest: DatasetManifest, seed: int, *,
                 num_hosts: int = 1, host_index: int = 0) -> None:
        if not 0 <= host_index < num_hosts:
            raise ValueError(
                f"host_index {host_index} out of range for {num_hosts} hosts")
        self.manifest = manifest
        self.seed = int(seed)
        self.num_hosts = num_hosts
        self.host_index = host_index
        self._lock = threading.Lock()
        #: LRU of generated epoch sequences — keyed on USE, not on the
        #: epoch being generated: the planner's window walks several
        #: epochs ahead of the consumer each tick, and a relative
        #: eviction rule would thrash (regenerate O(n) permutations
        #: every tick, inside the scheduler's lock)
        self._cache: "OrderedDict[int, List[BlockRef]]" = OrderedDict()
        #: epoch -> {block id: position}, the inverse of a kept
        #: sequence; built when first asked for (never at job start)
        #: and dropped with its sequence
        self._positions: Dict[int, Dict[int, int]] = {}

    # -- sequences ----------------------------------------------------------
    def epoch_sequence(self, epoch: int) -> List[BlockRef]:
        """This host's exact access order for ``epoch`` (stable across
        calls and processes)."""
        with self._lock:
            return self._sequence(epoch)

    def _sequence(self, epoch: int) -> List[BlockRef]:
        """``epoch_sequence`` under the caller's hold of the lock."""
        seq = self._cache.get(epoch)
        if seq is not None:
            self._cache.move_to_end(epoch)
            return seq
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, int(epoch)]))
        perm = rng.permutation(len(self.manifest.blocks))
        seq = [self.manifest.blocks[i]
               for i in perm[self.host_index::self.num_hosts]]
        self._cache[epoch] = seq
        while len(self._cache) > _EPOCH_CACHE_SIZE:
            self._positions.pop(self._cache.popitem(last=False)[0], None)
        return seq

    def _position_in(self, epoch: int) -> Dict[int, int]:
        """Where this host reads each block in ``epoch``: the inverse
        of :meth:`epoch_sequence`."""
        with self._lock:
            seq = self._sequence(epoch)
            inverse = self._positions.get(epoch)
            if inverse is None:
                inverse = self._positions[epoch] = {
                    ref.block_id: i for i, ref in enumerate(seq)}
            return inverse

    def next_use(self, block_id: int, epoch: int, pos: int) -> int:
        """Global sequence number of this host's first access of
        ``block_id`` at or after ``(epoch, pos)``: the rest of this
        epoch, then the epochs that follow, as many as the oracle keeps
        (a strided shard may skip a block for whole epochs); ``NEVER``
        beyond them."""
        for e in range(epoch, epoch + _EPOCH_CACHE_SIZE):
            at = self._position_in(e).get(block_id)
            if at is not None and (e > epoch or at >= pos):
                return self.global_seq(e, at)
        return NEVER

    def epoch_len(self) -> int:
        """Accesses this host makes per epoch."""
        n, h = len(self.manifest.blocks), self.num_hosts
        return (n - self.host_index + h - 1) // h

    def global_seq(self, epoch: int, pos: int) -> int:
        """Monotone global sequence number of access ``pos`` in ``epoch``
        (the deadline currency the scheduler tracks lateness in)."""
        return epoch * self.epoch_len() + pos

    def window(self, epoch: int, pos: int,
               k: int) -> List[Tuple[int, BlockRef]]:
        """The next ``k`` accesses at-or-after ``(epoch, pos)`` as
        ``(global_seq, ref)`` pairs, continuing into subsequent epochs —
        the clairvoyant lookahead the scheduler plans from."""
        out: List[Tuple[int, BlockRef]] = []
        per_epoch = self.epoch_len()
        if per_epoch == 0:
            return out
        e, p = epoch, pos
        while len(out) < k:
            seq = self.epoch_sequence(e)
            while p < len(seq) and len(out) < k:
                out.append((self.global_seq(e, p), seq[p]))
                p += 1
            e, p = e + 1, 0
        return out
