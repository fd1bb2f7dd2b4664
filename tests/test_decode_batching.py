"""Microbatching decode scheduler: bucketed batch-N decode agrees with
batch-1 per image within the fast path's ±1-LSB uint8 contract (each
bucket is its own compiled program), duplicate in-flight oids single-flight
into one decode, and node-name parsing is strict."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.compression.latentcodec import compress_latent, decompress_latent
from repro.core.latent_store import LatentStore
from repro.core.tuner import TunerConfig
from repro.serve.engine import (DecodeBatcher, EngineConfig, ServingEngine,
                                _node_index)
from repro.vae.model import VAE, VAEConfig

TINY = VAEConfig(name="tiny", latent_channels=4, block_out_channels=(16, 32),
                 layers_per_block=1, groups=4)
N_OBJECTS = 12


@pytest.fixture(scope="module")
def vae():
    return VAE(TINY, seed=0)


@pytest.fixture(scope="module")
def store(vae):
    rng = np.random.default_rng(7)
    st = LatentStore(seed=1)
    for oid in range(N_OBJECTS):
        img = jnp.asarray(rng.standard_normal((1, 16, 16, 3)), jnp.float32)
        z = np.asarray(vae.encode_mean(img)).astype(np.float16)[0]
        st.put(oid, compress_latent(z))
    return st


def assert_within_one_lsb(img, want):
    """The uint8 fast path's contract across buckets: every batch size is
    its own compiled program, so pixels agree within ±1 LSB."""
    assert img.dtype == np.uint8 and img.shape == want.shape
    assert np.abs(img.astype(np.int16) - want.astype(np.int16)).max() <= 1


def make_engine(vae, store, **kw):
    cfg = EngineConfig(n_nodes=2, cache_bytes_per_node=1e5,
                       tuner=TunerConfig(window=50, step=0.02), **kw)
    # image_bytes = real uint8 nbytes of a 16x16x3 decode (the engine
    # corrects the charge to the stored array's nbytes anyway)
    return ServingEngine(vae, store, cfg, image_bytes=768.0, latent_bytes=6e2)


class TestBitIdenticalBatching:
    def test_batched_equals_batch1_per_image(self, vae, store):
        """get_many over N cold misses (one bucketed decode) returns the
        pixels of N separate get calls on a fresh engine, within ±1 LSB."""
        oids = list(range(8))
        batched = make_engine(vae, store).get_many(oids)
        sequential_eng = make_engine(vae, store)
        for oid, (img_b, _) in zip(oids, batched):
            img_1, _ = sequential_eng.get(oid)
            assert_within_one_lsb(img_b, img_1)

    def test_padded_bucket_equals_batch1(self, vae, store):
        """3 misses pad to the 4-bucket; padding must not perturb outputs
        beyond the cross-bucket ±1 LSB."""
        eng = make_engine(vae, store)
        res = eng.get_many([0, 1, 2])
        assert eng.batcher.stats["padded_slots"] == 1
        for oid, (img, _) in zip([0, 1, 2], res):
            z = decompress_latent(store.get(oid))
            direct = np.asarray(vae.decode_u8(
                jnp.asarray(z, jnp.float32)[None]))[0]
            assert_within_one_lsb(img, direct)

    def test_batched_results_match_direct_decode(self, vae, store):
        """Batches of 8 and 4 against batch-1 decodes: a bucketed decode
        is a different compiled program from batch 1, so the contract is
        the uint8 fast path's ±1 LSB, not bit identity."""
        eng = make_engine(vae, store)
        res = eng.get_many(list(range(N_OBJECTS)))   # > max bucket: 2 batches
        assert eng.batcher.stats["batches"] == 2
        for oid, (img, _) in zip(range(N_OBJECTS), res):
            z = decompress_latent(store.get(oid))
            direct = np.asarray(vae.decode_u8(
                jnp.asarray(z, jnp.float32)[None]))[0]
            assert_within_one_lsb(img, direct)


class TestSingleFlight:
    def test_duplicate_oids_decode_once(self, vae, store):
        eng = make_engine(vae, store)
        res = eng.get_many([5, 5, 5, 5])
        assert eng.batcher.stats["decodes"] == 1
        assert eng.batcher.stats["coalesced"] == 3
        ref = res[0][0]
        for img, _ in res[1:]:
            np.testing.assert_array_equal(img, ref)

    def test_mixed_duplicates_and_uniques(self, vae, store):
        eng = make_engine(vae, store)
        res = eng.get_many([1, 2, 1, 3, 2, 1])
        assert eng.batcher.stats["decodes"] == 3
        assert eng.batcher.stats["coalesced"] == 3
        assert len(res) == 6
        s = eng.summary()
        assert s["total"] == 6 and s["coalesced_decodes"] == 3

    def test_tuner_sees_per_image_ms(self, vae, store):
        eng = make_engine(vae, store)
        eng.get_many([0, 1, 2, 3])
        assert any(n.tuner.t_decode._initialized for n in eng.nodes)


class TestBucketing:
    def test_bucket_for(self, vae):
        b = DecodeBatcher(vae, buckets=(1, 2, 4, 8))
        assert [b.bucket_for(n) for n in (1, 2, 3, 4, 5, 8)] == \
            [1, 2, 4, 4, 8, 8]

    def test_flush_chunks_at_max_bucket(self, vae, store):
        eng = make_engine(vae, store, decode_buckets=(1, 2))
        eng.get_many(list(range(5)))                 # 2 + 2 + 1
        assert eng.batcher.stats["batches"] == 3
        assert eng.batcher.stats["padded_slots"] == 0

    def test_bad_buckets_rejected(self, vae):
        with pytest.raises(ValueError):
            DecodeBatcher(vae, buckets=())
        with pytest.raises(ValueError):
            DecodeBatcher(vae, buckets=(0, 2))


class TestNodeIndex:
    def test_parses(self):
        assert _node_index("node0") == 0
        assert _node_index("node17") == 17

    @pytest.mark.parametrize("bad", ["node", "peer3", "nodex", "3"])
    def test_rejects_malformed(self, bad):
        with pytest.raises(ValueError):
            _node_index(bad)


class TestAbortedWindow:
    def test_unknown_oid_does_not_leak_pending_decodes(self, vae, store):
        """A KeyError mid-window must not leave queued decodes or queue
        depth behind for the next window."""
        eng = make_engine(vae, store)
        with pytest.raises(KeyError):
            eng.get_many([0, 1, N_OBJECTS + 99])
        assert len(eng.batcher) == 0
        assert all(n.queue_depth == 0 for n in eng.nodes)
        decodes_before = eng.batcher.stats["decodes"]
        res = eng.get_many([2, 3])
        assert eng.batcher.stats["decodes"] == decodes_before + 2
        for oid, (img, _) in zip([2, 3], res):
            z = decompress_latent(store.get(oid))
            direct = np.asarray(vae.decode_u8(
                jnp.asarray(z, jnp.float32)[None]))[0]
            assert_within_one_lsb(img, direct)


class TestEngineStillServes:
    def test_hit_composition_improves(self, vae, store):
        """Repeated zipf traffic through the batched path still builds
        image hits (regression guard on the rewritten read path)."""
        rng = np.random.default_rng(0)
        eng = make_engine(vae, store)
        ids = rng.zipf(1.4, 300) % N_OBJECTS
        outcomes = []
        for start in range(0, len(ids), 8):          # 8-request windows
            outcomes += [o for _, o in
                         eng.get_many([int(i) for i in
                                       ids[start:start + 8]])]
        s = eng.summary()
        assert s["total"] == 300
        assert s["image_hit"] > 0
        assert sum(o != "full_miss" for o in outcomes[-100:]) > 50
