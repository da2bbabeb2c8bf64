"""Plain NumPy reference of the record-batching path, independent of
``alluxio_tpu.client.jax_io.batched_device_iterator`` and
``alluxio_tpu.ops.decode``: shards -> rows in order -> batches.

A shard is a run of fixed-size records followed by padding (records
never straddle a shard); a pass is every shard's rows in shard order,
cut into batches of ``batch_size``; the last partial batch is dropped
or kept. A record is ``label (4 B little-endian) || H*W*C uint8``."""

from __future__ import annotations

import numpy as np

MEAN = (0.485, 0.456, 0.406)
STD = (0.229, 0.224, 0.225)


def shard_rows(shard, record_bytes: int) -> np.ndarray:
    """``(rows, record_bytes)`` uint8 of one shard, padding skipped."""
    flat = np.frombuffer(bytes(shard), np.uint8)
    rows = flat.size // record_bytes
    return flat[:rows * record_bytes].reshape(rows, record_bytes)


def batches(shards, record_bytes: int, batch_size: int,
            drop_remainder: bool = True) -> list:
    """Every batch of one pass over ``shards``, as a plain loop."""
    out, pending = [], []
    for shard in shards:
        for row in shard_rows(shard, record_bytes):
            pending.append(row)
            if len(pending) == batch_size:
                out.append(np.stack(pending))
                pending = []
    if pending and not drop_remainder:
        out.append(np.stack(pending))
    return out


def decode(records: np.ndarray, height: int, width: int,
           channels: int = 3):
    """``(images, labels)``: labels from the four label bytes,
    little-endian; pixels ``/255``, normalised in f32, rounded to
    bf16 (returned as f32 holding the rounded values)."""
    import ml_dtypes

    lab = records[:, :4].astype(np.int64)
    labels = (lab[:, 0] | lab[:, 1] << 8 | lab[:, 2] << 16
              | lab[:, 3] << 24).astype(np.int32)
    x = records[:, 4:4 + height * width * channels].reshape(
        -1, height, width, channels).astype(np.float32)
    x = (x / np.float32(255.0) - np.asarray(MEAN, np.float32)) \
        / np.asarray(STD, np.float32)
    return x.astype(ml_dtypes.bfloat16).astype(np.float32), labels
