"""Pallas TPU kernel: masked row copies out of an HBM-resident shard.

The local half of the mesh warm set's batch assembly
(``parallel/ici_store.py``): of a device's ``(per_dev, elems)`` shard,
``B`` rows named by index land in a ``(B, elems)`` buffer, a row of
zeros wherever the device is not the row's owner. XLA has no good
lowering for it on the chip: the shard of a uint8 cache lives in HBM as
``u8[per_dev, elems]{T(8,128)(4,1)}``, tiles of 8 rows x 128 columns
with FOUR ROWS packed into each 32-bit word, so one row is not a run of
bytes. ``jnp.take`` becomes a thousand small gathers (8.6 ms for 8 rows
of 32 MiB on a v5e), a ``dynamic_slice`` a row unpacks bytes at a third
of the HBM's rate (6.0 ms), and Mosaic refuses a one-row DMA ("slice
along dimension 0 must be aligned to tiling (8)").

So the kernel moves what the layout allows, the aligned GROUP of 8 rows
that holds a wanted row, a column chunk at a time through the gridded
``BlockSpec`` pipeline, and picks the row out in VMEM with 32-bit shifts
on the packed words (a ref ``bitcast`` to uint32: no byte shuffles). The
mask is folded into the fetch: a row the device does not own keeps its
block index at (0, 0) for the whole grid, which the pipeline fetches
once, so only owned rows cost HBM reads (8 x their bytes) and every row
costs its write. Measured alone on one v5e chip, 8 rows of 32 MiB out of
a (128, 32 MiB) uint8 shard: 1.29 ms with 2 rows owned, 3.33 ms with
all 8 (PERF.md section 6, PR 34).

Mosaic compiles it on a TPU; anywhere else pass ``interpret=True``.
"""

from __future__ import annotations

#: rows of one HBM tile: the least a DMA may slice along rows, and the
#: output rows one grid step builds
_GROUP = 8
#: columns of one tile: a block's width is a multiple of it
_LANES = 128
#: bytes of a row one grid step moves. 9 input/output blocks of
#: 8 x 64 KiB, double-buffered, and the word scratch are 9.5 MiB of
#: VMEM, inside Mosaic's scoped default (16 MiB on a v5e); 32 KiB
#: measured 12% slower, 128 and 256 KiB the same
_CHUNK_BYTES = 64 << 10


def masked_rows(local, rows, mine, *, interpret: bool = False):
    """``(B, elems)`` of ``local``'s dtype: row ``r`` is
    ``local[rows[r]]`` where ``mine[r]``, zeros elsewhere.

    ``local``: ``(n, elems)``, elements of 1, 2 or 4 bytes; ``rows``:
    ``(B,)`` int32, in ``[0, n)`` wherever ``mine`` (a row that is not
    is never read, whatever its index); ``mine``: ``(B,)`` bool.
    Trace-time shapes, so calling this inside the consumer's ``jit``
    compiles it once a ``B``; the program does not grow with ``B`` (a
    grid axis walks the groups of 8)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    dtype = np.dtype(local.dtype)
    if dtype.itemsize not in (1, 2, 4) or dtype == np.bool_:
        raise TypeError(
            f"masked_rows moves elements of 1, 2 or 4 bytes inside "
            f"32-bit words; {dtype} is not one")
    bits = 8 * dtype.itemsize
    pack = 32 // bits          # rows of the shard in one 32-bit word
    words = _GROUP // pack     # word rows of one group
    elems = local.shape[1]
    # the last block of a row may hang over its end (reads past it are
    # never stored: the write of the overhang is dropped)
    chunk = min(_CHUNK_BYTES // dtype.itemsize,
                -(-elems // _LANES) * _LANES)
    batch = rows.shape[0]
    groups = -(-batch // _GROUP)
    pad = groups * _GROUP - batch
    # scalar-prefetched, a row of the padded batch each: the group that
    # holds it (block index along rows), its place in the group, owned?
    owned = jnp.pad(mine.astype(jnp.int32), (0, pad))
    rows = jnp.pad(rows.astype(jnp.int32), (0, pad))
    group_of = jnp.where(owned != 0, rows // _GROUP, 0)
    place = rows % _GROUP

    def in_map(k):
        def index(g, j, group_of, place, owned):
            r = g * _GROUP + k
            # not owned: block (0, 0) all along, fetched once
            return group_of[r], jnp.where(owned[r] != 0, j, 0)
        return index

    def kernel(group_of, place, owned, *refs):
        del group_of  # the index maps' alone
        *ins, out, word_rows = refs
        base = pl.program_id(0) * _GROUP
        for w in range(words):
            acc = jnp.zeros((1, chunk), jnp.uint32)
            for q in range(pack):
                k = w * pack + q
                at = place[base + k]
                word = ins[k].bitcast(jnp.uint32)[pl.ds(at // pack, 1), :]
                shift = ((at % pack) * bits).astype(jnp.uint32)
                val = (word >> shift) & np.uint32((1 << bits) - 1)
                val = jnp.where(owned[base + k] != 0, val, np.uint32(0))
                acc = acc | (val << np.uint32(q * bits))
            word_rows[pl.ds(w, 1), :] = acc
        out[...] = pltpu.bitcast(word_rows[...], out.dtype)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups * _GROUP, elems),
                                       local.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(groups, pl.cdiv(elems, chunk)),
            in_specs=[pl.BlockSpec((_GROUP, chunk), in_map(k))
                      for k in range(_GROUP)],
            out_specs=pl.BlockSpec((_GROUP, chunk),
                                   lambda g, j, *_: (g, j)),
            scratch_shapes=[pltpu.VMEM((words, chunk), jnp.uint32)]),
        interpret=interpret,
        name="atpu_masked_rows",
    )(group_of, place, owned, *([local] * _GROUP))
    return out[:batch] if pad else out
