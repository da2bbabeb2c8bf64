"""Plain NumPy reference of the mesh warm set, independent of
``alluxio_tpu.parallel.ici_store``: the host bytes of every block ->
the table a mesh of ``n_devices`` holds, who owns a global index, and
the rows a batch of global indices names.

The table has ``n_devices x per_dev`` rows of ``block_bytes``, ``per_dev``
= ceil(n_blocks / n_devices); global row ``g`` is block ``g``, owned by
mesh position ``g // per_dev``; a block shorter than ``block_bytes`` and
the rows past the last block are zero-padded."""

from __future__ import annotations

import numpy as np


def per_dev(n_blocks: int, n_devices: int) -> int:
    return -(-n_blocks // n_devices)


def table(blocks, n_devices: int, block_bytes: int) -> np.ndarray:
    """``(n_devices * per_dev, block_bytes)`` uint8, as a plain loop."""
    rows = n_devices * per_dev(len(blocks), n_devices)
    out = np.zeros((rows, block_bytes), np.uint8)
    for g, block in enumerate(blocks):
        data = np.frombuffer(bytes(block), np.uint8)
        out[g, :data.size] = data
    return out


def owner(g: int, n_blocks: int, n_devices: int) -> int:
    """Mesh position that holds global row ``g``."""
    return g // per_dev(n_blocks, n_devices)


def batch(tab: np.ndarray, indices) -> np.ndarray:
    """``(len(indices), block_bytes)``: row ``r`` is global row
    ``indices[r]`` (duplicates and padded rows included); an index that
    names no row of the table, negative or past its end, is a zero
    row."""
    zero = np.zeros(tab.shape[1], tab.dtype)
    return np.stack([tab[int(g)] if 0 <= int(g) < len(tab) else zero
                     for g in indices])
