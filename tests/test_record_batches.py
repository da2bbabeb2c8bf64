"""``batched_device_iterator`` against the plain NumPy reference
(``testutils/record_reference.py``): record shards of a LocalCluster
through ``DeviceBlockLoader`` into batches on the device. CPU only:
equality, counts of compile requests, counters and spans, never a
speed. The last tests compile the deployment's own shapes for the v5e
without a chip."""

import contextlib
import math

import numpy as np
import pytest

from alluxio_tpu.client import jax_io
from alluxio_tpu.client.jax_io import (
    DeviceBlockLoader, batched_device_iterator,
)
from alluxio_tpu.metrics import metrics
from alluxio_tpu.minicluster import LocalCluster
from alluxio_tpu.utils.tracing import set_tracing_enabled, tracer
from tests.testutils import record_reference as ref

BLOCK = 4096
PAD = 255  # records hold 0..254, so a padding byte in a batch shows


def make_shards(seed: int, n_shards: int, record_bytes: int,
                last_rows=None) -> list:
    """``n_shards`` shards of ``BLOCK`` bytes: as many records as fit,
    then padding; ``last_rows`` makes the last shard a shorter file."""
    rng = np.random.default_rng(seed)
    rows = BLOCK // record_bytes
    shards = []
    for i in range(n_shards):
        short = last_rows is not None and i == n_shards - 1
        n = last_rows if short else rows
        size = n * record_bytes + 3 if short else BLOCK
        shard = np.full(size, PAD, np.uint8)
        shard[:n * record_bytes] = rng.integers(
            0, PAD, n * record_bytes, dtype=np.uint8)
        shards.append(shard.tobytes())
    return shards


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    with LocalCluster(str(tmp_path_factory.mktemp("records")),
                      num_workers=1, block_size=BLOCK) as c:
        yield c


@contextlib.contextmanager
def loader_over(cluster, name: str, shards, **kw):
    fs = cluster.file_system()
    paths = []
    for i, shard in enumerate(shards):
        paths.append(f"/{name}/shard-{i:03d}")
        fs.write_all(paths[-1], shard)
    loader = DeviceBlockLoader(fs, paths, **kw)
    try:
        yield loader
    finally:
        loader.close()


def one_pass(loader, **kw) -> list:
    return [np.asarray(b) for b in batched_device_iterator(loader, **kw)]


def assert_same(got, want) -> None:
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == np.uint8 and g.shape == w.shape, i
        assert np.array_equal(g, w), f"batch {i} differs"


def _count(name: str) -> float:
    return metrics().snapshot().get(name, 0)


class CompileRequests:
    """Compile requests through JAX's own monitoring events, as
    ``benchmark/run.py::CompileLog`` counts them."""

    def __init__(self) -> None:
        import jax.monitoring as mon

        self.n = 0
        mon.register_event_duration_secs_listener(self._duration)

    def _duration(self, name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@pytest.fixture(scope="module")
def compile_requests():
    return CompileRequests()


# (case, record_bytes, batch, shards, rows of a shorter last shard)
CASES = [
    # rows a shard and batch coprime, more than one full carry cycle
    ("coprime-13x8", 300, 8, 17, None),
    ("coprime-41x16", 99, 16, 20, None),
    ("coprime-31x9", 130, 9, 19, None),
    # fewer rows a shard than a batch: the carry grows over blocks
    ("rows-under-batch-7x16", 580, 16, 23, None),
    ("rows-under-batch-3x32", 1200, 32, 25, None),
    # a shorter last shard
    ("short-last-13x8", 300, 8, 9, 5),
    ("short-last-7x16", 580, 16, 11, 2),
    # rows a multiple of the batch: the carry stays empty
    ("aligned-16x8", 256, 8, 4, None),
]


@pytest.mark.parametrize("drop_remainder", [True, False],
                         ids=["drop", "keep"])
@pytest.mark.parametrize("case,record_bytes,batch,n_shards,last_rows",
                         CASES, ids=[c[0] for c in CASES])
def test_every_batch_of_a_pass_equals_the_reference(
        cluster, case, record_bytes, batch, n_shards, last_rows,
        drop_remainder):
    rows = BLOCK // record_bytes
    if case.startswith("coprime"):
        assert math.gcd(rows, batch) == 1 and n_shards > batch
    shards = make_shards(7, n_shards, record_bytes, last_rows)
    want = ref.batches(shards, record_bytes, batch, drop_remainder)
    with loader_over(cluster, f"{case}-{drop_remainder}", shards) as loader:
        got = one_pass(loader, record_bytes=record_bytes, batch_size=batch,
                       drop_remainder=drop_remainder)
        assert_same(got, want)
        # padding never reaches a batch
        assert all(PAD not in b for b in got)
        # a second call is a second pass: it starts with an empty carry
        assert_same(one_pass(loader, record_bytes=record_bytes,
                             batch_size=batch,
                             drop_remainder=drop_remainder), want)
    total = sum(len(ref.shard_rows(s, record_bytes)) for s in shards)
    assert len(got) == (total // batch if drop_remainder
                        else -(-total // batch))


@pytest.mark.parametrize("rows,batch", [(13, 8), (41, 16), (7, 16), (3, 8),
                                        (16, 8), (1, 4)])
def test_no_slot_the_host_yields_can_clamp(rows, batch):
    """The block's program at every carried count: ``dynamic_slice``
    clamps a start that runs off the end, so every slot the host's
    arithmetic calls whole must read exactly its rows, and the new
    carry must end in the rows left over."""
    import jax

    record_bytes = 12
    rng = np.random.default_rng(rows * 100 + batch)
    assemble, _tail = jax_io._record_batch_programs(record_bytes, batch)
    block = rng.integers(0, 256, rows * record_bytes + 5, dtype=np.uint8)
    recs = block[:rows * record_bytes].reshape(rows, record_bytes)
    for carried in range(batch):
        carry = rng.integers(0, 256, (batch, record_bytes), dtype=np.uint8)
        stream = np.concatenate([carry[batch - carried:], recs])
        slots, new_carry = jax.device_get(
            assemble(carry, np.int32(carried), block))
        assert len(slots) == (batch - 1 + rows) // batch
        whole, left = divmod(carried + rows, batch)
        assert whole in (len(slots), len(slots) - 1)
        for j in range(whole):
            assert np.array_equal(slots[j],
                                  stream[j * batch:(j + 1) * batch])
        assert new_carry.shape == (batch, record_bytes)
        if left:
            assert np.array_equal(new_carry[batch - left:],
                                  stream[len(stream) - left:])


@pytest.mark.parametrize("drop_remainder", [True, False],
                         ids=["drop", "keep"])
def test_compile_requests_do_not_grow_with_the_number_of_shards(
        cluster, compile_requests, drop_remainder):
    record_bytes, batch = 315, 8  # 13 rows a shard, coprime with 8
    counts = {}
    for n_shards in (8, 64):
        shards = make_shards(n_shards, n_shards, record_bytes)
        # a process that has not batched these sizes yet
        jax_io._record_batch_programs.cache_clear()
        with loader_over(cluster, f"compiles-{drop_remainder}-{n_shards}",
                         shards) as loader:
            n0 = compile_requests.n
            got = one_pass(loader, record_bytes=record_bytes,
                           batch_size=batch, drop_remainder=drop_remainder)
            counts[n_shards] = compile_requests.n - n0
            assert_same(got, ref.batches(shards, record_bytes, batch,
                                         drop_remainder))
            # a new pass makes no compile request at all
            n0 = compile_requests.n
            one_pass(loader, record_bytes=record_bytes, batch_size=batch,
                     drop_remainder=drop_remainder)
            assert compile_requests.n == n0
    assert counts[8] == counts[64]
    assert 1 <= counts[8] <= 4


def test_closing_mid_pass_retires_the_producer(cluster):
    shards = make_shards(3, 12, 300)
    with loader_over(cluster, "close", shards, prefetch=1) as loader:
        it = batched_device_iterator(loader, record_bytes=300, batch_size=8)
        first = np.asarray(next(it))
        assert np.array_equal(first, ref.batches(shards[:1], 300, 8)[0])
        it.close()
        assert loader._producer_pool is None
        # and the loader serves a whole new pass afterwards
        assert_same(one_pass(loader, record_bytes=300, batch_size=8),
                    ref.batches(shards, 300, 8))


def test_decode_of_the_iterators_batches_equals_the_reference_decode(
        cluster):
    import jax

    from alluxio_tpu.ops.decode import (
        decode_image_records, encode_image_records, image_record_bytes,
    )

    h, w, c, batch = 5, 5, 3, 8
    record_bytes = image_record_bytes(h, w, c)
    rows = BLOCK // record_bytes
    assert math.gcd(rows, batch) == 1
    rng = np.random.default_rng(11)
    shards, labels = [], []
    for _ in range(9):
        lab = rng.integers(-2**31, 2**31, rows).astype(np.int32)
        imgs = rng.integers(0, 256, (rows, h, w, c), dtype=np.uint8)
        raw = encode_image_records(imgs, lab)
        shards.append(raw + bytes([PAD]) * (BLOCK - len(raw)))
        labels.append(lab)
    labels = np.concatenate(labels)
    want = ref.batches(shards, record_bytes, batch)
    with loader_over(cluster, "decode", shards) as loader:
        n = 0
        for i, rec_batch in enumerate(batched_device_iterator(
                loader, record_bytes=record_bytes, batch_size=batch)):
            imgs, lab = jax.device_get(decode_image_records(
                rec_batch, height=h, width=w, channels=c))
            want_imgs, want_lab = ref.decode(want[i], h, w, c)
            assert np.array_equal(lab, want_lab)
            assert np.array_equal(lab, labels[i * batch:(i + 1) * batch])
            # bf16: exact after rounding
            assert imgs.dtype.name == "bfloat16"
            assert np.array_equal(imgs.astype(np.float32), want_imgs)
            n += 1
        assert n == len(want) == 9 * rows // batch


@pytest.mark.parametrize("drop_remainder", [True, False],
                         ids=["drop", "keep"])
def test_counters_and_span_say_what_left_the_iterator(cluster,
                                                      drop_remainder):
    record_bytes, batch = 300, 8
    shards = make_shards(5, 10, record_bytes, last_rows=4)
    want = ref.batches(shards, record_bytes, batch, drop_remainder)
    set_tracing_enabled(True)
    tracer().clear()
    try:
        with loader_over(cluster, f"counted-{drop_remainder}",
                         shards) as loader:
            b0 = _count("Client.JaxRecordBatches")
            n0 = _count("Client.JaxRecordBatchBytes")
            got = one_pass(loader, record_bytes=record_bytes,
                           batch_size=batch, drop_remainder=drop_remainder)
        spans = [s for s in reversed(tracer().snapshot(limit=4000))
                 if s["name"] == "atpu.loader.batch_assemble"]
    finally:
        set_tracing_enabled(False)
        tracer().clear()
    assert_same(got, want)
    assert _count("Client.JaxRecordBatches") - b0 == len(want)
    assert _count("Client.JaxRecordBatchBytes") - n0 == \
        sum(b.nbytes for b in want)
    # one span a block, tagged with the rows it brought, the rows
    # carried into it and the whole batches it gave
    assert len(spans) == len(shards)
    carried = 0
    for span, shard in zip(spans, shards):
        rows = len(ref.shard_rows(shard, record_bytes))
        tags = span["tags"]
        assert (int(tags["rows"]), int(tags["carry_in"]),
                int(tags["batches"])) == \
            (rows, carried, (carried + rows) // batch)
        carried = (carried + rows) % batch


# ---- the deployment's own shapes, compiled for the v5e without a chip ------
@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def test_imagenet64_block_program_compiles_for_the_v5e(one_chip):
    """32 MiB shards of 12,292 B records into batches of 128: ONE
    program with 22 slots and the carry, and no 2-D array of a whole
    block's records in it (the slots are cut from the block's own 1-D
    bytes; a ``(2729, 12292)`` intermediate is a second copy of the
    block, and compiles ten times as long)."""
    import jax
    import jax.numpy as jnp

    record_bytes, batch, block_bytes = 12292, 128, 32 << 20
    assemble, _tail = jax_io._record_batch_programs(record_bytes, batch)
    compiled = assemble.lower(
        jax.ShapeDtypeStruct((batch, record_bytes), jnp.uint8,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((block_bytes,), jnp.uint8, sharding=one_chip),
    ).compile()
    slots, carry = compiled.out_info
    assert len(slots) == 22 and carry.shape == (batch, record_bytes)
    assert all(s.shape == (batch, record_bytes) for s in slots)
    text = compiled.as_text()
    assert "atpu_record_batch" in text.split("\n", 1)[0]
    rows = block_bytes // record_bytes
    assert f"u8[{rows},{record_bytes}]" not in text


@pytest.mark.parametrize("dtype,batch", [
    ("uint8", 8),     # the four-chip cell's own step
    ("uint8", 19),    # two groups of eight and a tail
    ("uint16", 8), ("int32", 8)])
def test_mesh_warmset_batch_program_compiles_for_four_v5e_chips(
        topo, dtype, batch):
    """``MeshBlockCache.batch_fn`` at the four-chip cell's shapes (128
    blocks of 32 MiB a chip): Mosaic takes the row-copy kernel on the
    shard as it lies in HBM (rows packed into words, no copy of the
    shard to another layout), and the exchange is one all-reduce."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from alluxio_tpu.parallel.ici_store import MeshBlockCache
    from alluxio_tpu.parallel.mesh import make_mesh

    block_bytes, per_dev = 32 << 20, 128
    mesh = make_mesh(devices=topo.devices)
    cache = MeshBlockCache(mesh, block_bytes=block_bytes, dtype=dtype)
    elems = block_bytes // np.dtype(dtype).itemsize
    text = cache.batch_fn(per_dev).lower(
        jax.ShapeDtypeStruct((4 * per_dev, elems), dtype,
                             sharding=NamedSharding(mesh, P("data", None))),
        jax.ShapeDtypeStruct((batch,), jnp.int32,
                             sharding=NamedSharding(mesh, P())),
    ).compile().as_text()
    assert "tpu_custom_call" in text and "atpu_masked_rows" in text
    assert text.count(" all-reduce(") + text.count(" all-reduce-start(") == 1
    assert " all-gather(" not in text and " gather(" not in text
    shard = f"[{per_dev},{elems}]"
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and shard in ln]
