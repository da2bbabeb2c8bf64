"""``ops/row_copy_kernel.masked_rows`` under the Pallas interpreter,
byte for byte against plain NumPy indexing: every element width the
kernel packs into 32-bit words, shards that are no multiple of a tile in
either direction, batches that are no multiple of a group. Bytes only,
never a speed; Mosaic's own compile of it is in
``test_record_batches.py``, beside the other v5e compiles."""

from __future__ import annotations

import numpy as np
import pytest

from alluxio_tpu.ops.row_copy_kernel import _CHUNK_BYTES, masked_rows


def _shard(rng, n: int, elems: int, dtype) -> np.ndarray:
    if np.issubdtype(dtype, np.floating):
        return rng.standard_normal((n, elems)).astype(dtype)
    info = np.iinfo(dtype)
    return rng.integers(info.min, info.max, size=(n, elems), dtype=dtype,
                        endpoint=True)


def _run(local, rows, mine):
    import jax

    return np.asarray(jax.jit(
        lambda a, r, m: masked_rows(a, r, m, interpret=True))(
            local, np.asarray(rows, np.int32), np.asarray(mine, bool)))


@pytest.mark.parametrize("dtype", [np.uint8, np.int8, np.uint16, np.int16,
                                   np.int32, np.uint32, np.float32])
def test_every_element_width_moves_its_bytes(dtype):
    rng = np.random.default_rng([1, np.dtype(dtype).itemsize])
    local = _shard(rng, 16, 1024, dtype)
    rows = rng.integers(0, 16, size=8)
    mine = np.array([1, 0, 1, 1, 0, 1, 0, 1], bool)
    got = _run(local, rows, mine)
    want = np.where(mine[:, None], local[rows], np.zeros((), dtype))
    assert got.dtype == dtype
    assert np.array_equal(got.view(np.uint8), want.view(np.uint8))


@pytest.mark.parametrize("n,elems,dtype,batch", [
    (3, 8192, np.uint8, 5),       # fewer rows than one group of 8
    (1, 8192, np.uint8, 1),       # one row, one wanted
    (13, 4096, np.uint16, 19),    # two groups of output rows and a tail
    (11, _CHUNK_BYTES + 4464, np.uint8, 9),  # a last chunk that hangs over
    (5, 1000, np.uint8, 3),       # a row narrower than a chunk, not 128s
    (7, 300, np.int32, 16),       # the same, whole words
    (128, 3 * _CHUNK_BYTES, np.uint8, 24),  # three chunks, three groups
])
def test_shapes_off_the_tile_and_off_the_group(n, elems, dtype, batch):
    rng = np.random.default_rng([2, n, elems, batch])
    local = _shard(rng, n, elems, dtype)
    rows = rng.integers(0, n, size=batch)
    mine = rng.random(batch) < 0.6
    got = _run(local, rows, mine)
    want = np.where(mine[:, None], local[rows], np.zeros((), dtype))
    assert got.shape == (batch, elems)
    assert np.array_equal(got.view(np.uint8), want.view(np.uint8))


@pytest.mark.parametrize("mine", [[False] * 8, [True] * 8],
                         ids=["none_owned", "all_owned"])
def test_nothing_owned_is_zeros_and_everything_owned_is_a_plain_take(mine):
    rng = np.random.default_rng(3)
    local = _shard(rng, 24, 2048, np.uint8)
    rows = np.array([23, 0, 7, 8, 8, 15, 16, 23])  # group edges, a duplicate
    got = _run(local, rows, mine)
    want = local[rows] if mine[0] else np.zeros((8, 2048), np.uint8)
    assert np.array_equal(got, want)


def test_a_row_that_is_not_owned_is_never_read_whatever_its_index():
    rng = np.random.default_rng(4)
    local = _shard(rng, 8, 1024, np.uint8)
    rows = np.array([10 ** 6, 3, -5, 2 ** 31 - 1])
    mine = np.array([False, True, False, False])
    got = _run(local, rows, mine)
    assert np.array_equal(got[1], local[3])
    assert not got[[0, 2, 3]].any()


@pytest.mark.parametrize("dtype", [np.float64, np.int64, np.bool_])
def test_an_element_that_is_no_part_of_a_word_is_refused(dtype):
    local = np.zeros((8, 256), dtype)
    with pytest.raises(TypeError, match="1, 2 or 4 bytes"):
        masked_rows(local, np.zeros(4, np.int32), np.ones(4, bool),
                    interpret=True)
