"""JAX data-path + parallel tests on the virtual 8-device CPU mesh:
zero-copy loader, HBM page cache, decode ops, ring attention correctness,
sharded train step.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from alluxio_tpu.client.cache.hbm_store import HbmPageStore  # noqa: E402
from alluxio_tpu.client.cache.meta import PageId  # noqa: E402
from alluxio_tpu.models.train import (  # noqa: E402
    make_sharded_train_state, make_train_step,
)
from alluxio_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, forward, images_to_tokens, init_params,
)
from alluxio_tpu.ops.decode import (  # noqa: E402
    decode_image_records, encode_image_records, image_record_bytes,
)
from alluxio_tpu.parallel.mesh import make_mesh  # noqa: E402
from alluxio_tpu.parallel.ring_attention import (  # noqa: E402
    reference_attention, ring_attention,
)


class TestHbmStore:
    def test_put_get_pin_evict(self):
        store = HbmPageStore(capacity_bytes=4096)
        p1, p2 = PageId("f", 0), PageId("f", 1)
        assert store.put(p1, b"a" * 2048)
        assert store.put(p2, b"b" * 2048)
        lease = store.get(p1)
        assert lease is not None
        assert bytes(np.asarray(lease.array)[:2]) == b"aa"
        # full store + p1 pinned: p2 is the only evictable page
        assert store.put(PageId("f", 2), b"c" * 2048)
        assert store.has(p1) and not store.has(p2)
        lease.close()
        assert store.put(PageId("f", 3), b"d" * 4096)  # evicts everything
        assert not store.has(p1)

    def test_pinned_pages_block_oversized_put(self):
        store = HbmPageStore(capacity_bytes=1024)
        store.put(PageId("f", 0), b"x" * 1024)
        lease = store.get(PageId("f", 0))
        assert not store.put(PageId("f", 1), b"y" * 1024)  # all pinned
        lease.close()
        assert store.put(PageId("f", 1), b"y" * 1024)

    def test_eviction_keeps_consumer_array_alive(self):
        """Regression: eviction drops only the store's reference — an
        array a consumer obtained earlier must stay readable after its
        page is evicted (no arr.delete() under the consumer)."""
        store = HbmPageStore(capacity_bytes=1024)
        p0 = PageId("f", 0)
        store.put(p0, b"k" * 1024)
        with store.get(p0) as lease:
            held = lease.array
        # unpinned now; force p0 out by inserting a full-size page
        assert store.put(PageId("f", 1), b"m" * 1024)
        assert not store.has(p0)
        # the consumer's array is still valid device memory
        assert bytes(np.asarray(held)[:2]) == b"kk"


class TestHbmStoreByNextUse:
    """The tier under a loader that knows its order: a store handed a
    ``NextUseCacheEvictor`` (the loader does the handing; here a
    scripted order stands for the oracle)."""

    PAGE = 256

    def _store(self, order, pages: int):
        from alluxio_tpu.client.cache.evictor import NextUseCacheEvictor

        state = {"cursor": 0}

        def next_use(page_id, served):
            start = state["cursor"] + served
            return next((t for t in range(start, len(order))
                         if order[t] == page_id.page_index), 1 << 40)

        store = HbmPageStore(capacity_bytes=pages * self.PAGE,
                             evictor=NextUseCacheEvictor(next_use))
        return store, state

    def _put(self, store, i):
        return store.put(PageId("f", i), bytes([i]) * self.PAGE)

    def test_the_page_read_farthest_ahead_goes_and_has_touches_nothing(
            self):
        store, _state = self._store([3, 0, 1, 2], pages=3)
        for i in (0, 1, 2):  # 2 is the newest, and read last
            assert self._put(store, i)
        assert store.has(PageId("f", 0))  # no side effect to need
        assert self._put(store, 3)
        assert [store.has(PageId("f", i)) for i in range(4)] == \
            [True, True, False, True]

    def test_a_pinned_page_stays_whatever_its_key(self):
        store, _state = self._store([0, 1], pages=2)
        assert self._put(store, 0) and self._put(store, 7)  # 7: never
        with store.get(PageId("f", 7)):
            assert self._put(store, 1)  # 0 goes: 7 is pinned
            assert store.has(PageId("f", 7))
            assert not store.has(PageId("f", 0))
        assert self._put(store, 0)  # unpinned: now 7 goes first
        assert not store.has(PageId("f", 7))
        assert store.used_bytes == 2 * self.PAGE

    def test_three_threads_on_one_tier_keep_it_whole(self):
        """The producer's look-ups, the consumer's adopts and the
        agent's placements meet in the store: capacity holds, the
        evictor's list stays one sorted entry a page."""
        import random
        import sys
        import threading
        import time

        n, pages = 24, 8
        rng = np.random.default_rng(36)
        order = [int(x) for _ in range(40) for x in rng.permutation(n)]
        store, state = self._store(order, pages=pages)
        cap = pages * self.PAGE
        stop = time.monotonic() + 3.0
        errors = []

        def guard(fn):
            def run():
                try:
                    while time.monotonic() < stop and not errors:
                        fn()
                except BaseException as e:  # noqa: BLE001 - asserted
                    errors.append(e)
            return run

        def producer():  # look up, then move the cursor
            at = state["cursor"]
            lease = store.get(PageId("f", order[at]))
            if lease is not None:
                lease.close()
            state["cursor"] = (at + 1) % (len(order) - n)

        def adopter():  # what was read a little while ago, or ahead
            at = state["cursor"] + random.randint(-3, 5)
            self._put(store, order[max(0, at)])
            assert store.used_bytes <= cap

        def checker():
            ev = store._evictor
            with store._lock:
                assert ev._by_use == sorted(ev._by_use)
                assert {e[2] for e in ev._by_use} == set(ev._entry) \
                    == set(store._pages)
                assert len(ev._by_use) == len(ev._entry)

        threads = [threading.Thread(target=guard(fn))
                   for fn in (producer, adopter, adopter, checker)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(interval)
        assert errors == []
        assert store.page_count == pages  # full, and never over


class TestDecode:
    def test_image_record_round_trip(self):
        rng = np.random.default_rng(0)
        imgs = rng.integers(0, 256, size=(4, 8, 8, 3), dtype=np.uint8)
        labels = np.array([3, 1, 4, 999], dtype=np.int32)
        blob = encode_image_records(imgs, labels)
        rb = image_record_bytes(8, 8, 3)
        records = jnp.asarray(
            np.frombuffer(blob, dtype=np.uint8).reshape(4, rb))
        decoded, out_labels = decode_image_records(records, height=8, width=8)
        assert decoded.shape == (4, 8, 8, 3)
        assert decoded.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(out_labels), labels)

    def test_patchify_shapes(self):
        imgs = jnp.zeros((2, 32, 32, 3), jnp.bfloat16)
        tokens = images_to_tokens(imgs, patch=16)
        assert tokens.shape == (2, 4, 16 * 16 * 3)


class TestRingAttention:
    def test_matches_reference(self):
        mesh = make_mesh({"data": 8})
        rng = np.random.default_rng(1)
        b, t, h, d = 2, 64, 4, 16
        q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)),
                               dtype=jnp.float32) for _ in range(3))
        ref = reference_attention(q, k, v, causal=True)
        out = ring_attention(q, k, v, mesh=mesh, axis="data", causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_non_causal_matches(self):
        mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
        rng = np.random.default_rng(2)
        b, t, h, d = 1, 32, 2, 8
        q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)),
                               dtype=jnp.float32) for _ in range(3))
        ref = reference_attention(q, k, v, causal=False)
        out = ring_attention(q, k, v, mesh=mesh, axis="data", causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)


class TestShardedTraining:
    def test_dp_tp_train_step_runs_and_learns(self):
        cfg = TransformerConfig(vocab_or_patch_dim=48, d_model=32, n_heads=4,
                                d_ff=64, n_layers=2, n_classes=10, max_len=16)
        mesh = make_mesh({"data": 4, "model": 2})
        params, opt_state, tx, shardings = make_sharded_train_state(
            cfg, mesh, learning_rate=1e-2)
        step = make_train_step(cfg, mesh, tx, shardings)
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(rng.standard_normal((8, 16, 48)),
                             dtype=jnp.float32)
        labels = jnp.asarray(rng.integers(0, 10, size=(8,)), dtype=jnp.int32)
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, tokens, labels)
            losses.append(float(loss))
        assert losses[-1] < losses[0]  # it actually optimizes

    def test_forward_single_device_matches_sharded(self):
        cfg = TransformerConfig(vocab_or_patch_dim=24, d_model=16, n_heads=2,
                                d_ff=32, n_layers=1, n_classes=4, max_len=8)
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.ones((4, 8, 24), jnp.float32)
        local = forward(params, tokens, cfg)
        mesh = make_mesh({"data": 4, "model": 2})
        from jax.sharding import NamedSharding, PartitionSpec as P

        from alluxio_tpu.models.transformer import param_shardings

        sharded_params = jax.device_put(
            params, jax.tree.map(
                lambda s: NamedSharding(mesh, s), param_shardings(cfg),
                is_leaf=lambda x: isinstance(x, P)))
        sharded_tokens = jax.device_put(
            tokens, NamedSharding(mesh, P("data")))
        out = jax.jit(lambda p, t: forward(p, t, cfg))(
            sharded_params, sharded_tokens)
        np.testing.assert_allclose(np.asarray(local), np.asarray(out),
                                   rtol=2e-2, atol=2e-2)
