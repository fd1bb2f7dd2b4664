"""The uint8 regeneration fast path: fused-epilogue decode within +-1 LSB
of the f32 reference on every bucket (padded slots included), pipelined
flush bit-identical to the sequential flush, decompression memoized (a
coalesced or repeated oid never pays host DEFLATE twice), and the pixel
tier charged the stored array's real uint8 bytes."""

import types

import numpy as np
import pytest

import jax.numpy as jnp

from repro.compression.latentcodec import compress_latent, decompress_latent
from repro.core.dual_cache import DualFormatCache
from repro.core.regen_tier import Recipe
from repro.core.tuner import TunerConfig
from repro.kernels.ref import quantize_u8_ref
from repro.serve.engine import DecodeBatcher, EngineConfig, ServingEngine
from repro.core.latent_store import LatentStore
from repro.store import LatentBox, StoreConfig
from repro.vae.model import VAE, VAEConfig

TINY = VAEConfig(name="tiny", latent_channels=4, block_out_channels=(16, 32),
                 layers_per_block=1, groups=4)
N_OBJECTS = 12
LATENT_HWC = (8, 8, 4)          # 16x16x3 images (768 uint8 bytes)


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


def make_engine(vae, store, **kw):
    base = dict(n_nodes=2, cache_bytes_per_node=1e5,
                tuner=TunerConfig(window=50, step=0.02))
    base.update(kw)
    return ServingEngine(vae, store, EngineConfig(**base), image_bytes=768.0,
                         latent_bytes=6e2)


class TestUint8WithinOneLsb:
    @pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 8])
    def test_every_bucket_padded_slots_included(self, vae, store, n):
        """Window sizes covering every bucket (3 and 5 pad): the uint8
        fast path stays within +-1 LSB of quantizing the f32 reference
        decode for every slot."""
        eng = make_engine(vae, store)
        res = eng.get_many(list(range(n)))
        for oid, (img, _) in zip(range(n), res):
            assert img.dtype == np.uint8
            z = decompress_latent(store.get(oid))
            f32 = np.asarray(vae.decode(jnp.asarray(z, jnp.float32)[None]))[0]
            want = np.asarray(quantize_u8_ref(f32))
            lsb = np.abs(img.astype(np.int16) - want.astype(np.int16))
            assert lsb.max() <= 1

    def test_float32_mode_still_served(self, vae, store):
        """pixel_format='float32' keeps the legacy float pixels."""
        eng = make_engine(vae, store, pixel_format="float32")
        img, _ = eng.get(0)
        assert img.dtype == np.float32
        z = decompress_latent(store.get(0))
        direct = np.asarray(vae.decode(jnp.asarray(z, jnp.float32)[None]))[0]
        np.testing.assert_array_equal(img, direct)


class TestPipelinedFlush:
    def test_pipelined_bit_identical_to_sequential(self, vae, store):
        """Async-dispatch pipelining is a scheduling change only: the
        decoded bytes match the sequential flush exactly."""
        node = types.SimpleNamespace(tuner=None)
        results = {}
        for pipeline in (False, True):
            b = DecodeBatcher(vae, (1, 2, 4, 8), pipeline=pipeline)
            for oid in range(N_OBJECTS):       # 12 oids -> 8 + 4 chunks
                b.submit(oid, store.get(oid), node)
            results[pipeline] = b.flush()
        assert results[False].keys() == results[True].keys()
        for oid in results[False]:
            np.testing.assert_array_equal(results[False][oid],
                                          results[True][oid])

    def test_prewarm_compiles_all_buckets(self, vae, store):
        want = {(bk, LATENT_HWC) for bk in (1, 2, 4, 8)}
        b = DecodeBatcher(vae, (1, 2, 4, 8))
        secs = b.prewarm(LATENT_HWC)
        assert b._warm == want
        assert set(secs) == {1, 2, 4, 8}
        assert b.prewarm(LATENT_HWC) == {1: 0.0, 2: 0.0, 4: 0.0, 8: 0.0}
        eng = make_engine(vae, store)
        eng.prewarm_decode(LATENT_HWC)
        assert eng.batcher._warm == want

    def test_new_latent_shape_warms_before_it_is_timed(self, vae):
        """A batcher warmed for one latent shape meets a second shape: the
        compile lands in warm-up, outside the timed decode that feeds the
        tuner's EWMA."""
        node = types.SimpleNamespace(tuner=None)
        b = DecodeBatcher(vae, (1, 2))
        b.prewarm(LATENT_HWC)
        other = (4, 4, LATENT_HWC[2])
        calls = []
        decode = b._decode_fn

        def spy(zb):
            calls.append(((int(zb.shape[0]), tuple(zb.shape[1:])),
                          set(b._warm)))
            return decode(zb)

        b._decode_fn = spy
        rng = np.random.default_rng(0)
        b.submit(0, compress_latent(
            rng.standard_normal(other).astype(np.float16)), node)
        b.flush()
        assert (1, other) in b._warm
        # first call is the warm-up (shape not yet warm); the timed decode
        # that follows already finds it warm
        assert calls[0] == ((1, other), {(bk, LATENT_HWC) for bk in (1, 2)})
        assert calls[1][0] == (1, other) and (1, other) in calls[1][1]


class TestDecompressionMemo:
    def test_coalesced_oid_never_decompresses_twice(self, vae, store):
        """Single-flight duplicates within a window and repeats across
        windows both hit the memo: one DEFLATE per distinct blob."""
        eng = make_engine(vae, store)
        eng.get_many([5, 5, 5, 5])
        assert eng.batcher.stats["decompressions"] == 1
        assert eng.batcher.stats["coalesced"] == 3
        # the pixel tier may now serve 5 from cache; force decodes via
        # fresh oids plus the repeat to exercise the cross-window memo
        eng.get_many([5, 6, 7])
        assert eng.batcher.stats["decompressions"] <= 3
        counts = eng.batcher.stats
        assert counts["memo_hits"] + counts["decompressions"] >= 3

    def test_repeat_windows_hit_memo(self, vae, store):
        """An object decoding once per window (latent-hit traffic) pays
        host DEFLATE only on its first window."""
        # pixel tier too small for these images -> every read re-decodes
        eng = make_engine(vae, store, cache_bytes_per_node=2e3, alpha0=0.1)
        for _ in range(4):
            eng.get_many([1])
        assert eng.batcher.stats["decodes"] == 4
        assert eng.batcher.stats["decompressions"] == 1
        assert eng.batcher.stats["memo_hits"] == 3

    def test_memo_invalidated_on_reput(self, vae, store):
        """delete + re-put with different pixels must not serve the stale
        memoized latent."""
        rng = np.random.default_rng(3)
        st = LatentStore(seed=1)
        vae_local = vae
        img_a = jnp.asarray(rng.standard_normal((1, 16, 16, 3)), jnp.float32)
        z_a = np.asarray(vae_local.encode_mean(img_a)).astype(np.float16)[0]
        st.put(0, compress_latent(z_a))
        eng = make_engine(vae_local, st, cache_bytes_per_node=2e3, alpha0=0.1)
        first, _ = eng.get(0)
        eng.delete(0)
        img_b = jnp.asarray(rng.standard_normal((1, 16, 16, 3)), jnp.float32)
        eng.put(0, image=img_b)
        second, _ = eng.get(0)
        want = eng.batcher.decode_single(np.asarray(
            decompress_latent(st.get(0)), np.float32))
        np.testing.assert_array_equal(second, want)
        assert not np.array_equal(first, second)

    def test_overwrite_put_purges_cached_copies(self, vae):
        """Re-putting an oid WITHOUT deleting first must not serve stale
        pixels from any cache tier (pixel payload, latent blob, or memo)."""
        rng = np.random.default_rng(11)
        st = LatentStore(seed=1)
        img_a = jnp.asarray(rng.standard_normal((1, 16, 16, 3)), jnp.float32)
        z_a = np.asarray(vae.encode_mean(img_a)).astype(np.float16)[0]
        st.put(0, compress_latent(z_a))
        eng = make_engine(vae, st, promote_threshold=1)
        for _ in range(3):              # miss -> promote -> pixel hit
            stale, _ = eng.get(0)
        img_b = jnp.asarray(rng.standard_normal((1, 16, 16, 3)), jnp.float32)
        eng.put(0, image=img_b)         # overwrite, no delete
        fresh, _ = eng.get(0)
        want = eng.batcher.decode_single(np.asarray(
            decompress_latent(st.get(0)), np.float32))
        np.testing.assert_array_equal(fresh, want)
        assert not np.array_equal(stale, fresh)

    def test_memo_disabled(self, vae, store):
        eng = make_engine(vae, store, cache_bytes_per_node=2e3, alpha0=0.1)
        eng.batcher.memo_entries = 0
        for _ in range(3):
            eng.get_many([2])
        assert eng.batcher.stats["decompressions"] == 3


class TestRealPixelBytes:
    def test_dual_cache_resize_in_place(self):
        """set_image_nbytes corrects the charge without LRU reorder."""
        c = DualFormatCache(10_000, alpha=1.0, image_size_fn=lambda _: 3072)
        for oid in (1, 2):
            c.insert_image(oid)
        assert c.image_tier.resident_bytes == 6144
        assert c.set_image_nbytes(1, 768)
        assert c.image_tier.size_of(1) == 768
        assert c.image_tier.resident_bytes == 768 + 3072
        # LRU order unchanged: 1 is still the eviction candidate
        evicted = {oid for oid, _ in c.image_tier.insert(3, 8000)}
        assert 1 in evicted
        assert not c.set_image_nbytes(99, 10)     # absent -> no-op

    def test_insert_with_real_nbytes(self):
        c = DualFormatCache(10_000, alpha=0.5)
        c.insert_image(7, nbytes=768)
        assert c.image_tier.size_of(7) == 768
        c.admit_latent(8, nbytes=100)
        assert c.latent_tier.size_of(8) == 100

    def test_engine_charges_real_uint8_bytes(self, vae, store):
        """Promoted pixels are charged 768 bytes (16x16x3 uint8), not the
        float32 3072 — and stat()/summary() surface it."""
        cfg = StoreConfig(n_nodes=2, cache_bytes_per_node=1e5,
                          image_bytes=768.0, latent_bytes=6e2,
                          promote_threshold=1,
                          tuner=TunerConfig(window=10**9))
        box = LatentBox.engine(vae=vae, config=cfg)
        box.put(0, recipe=Recipe(seed=1, height=16, width=16))
        for _ in range(3):                 # miss -> latent hit -> promote
            box.get(0)
        st = box.stat(0)
        assert any(r.startswith("image@") for r in st.residency)
        assert st.pixel_bytes == 768.0
        s = box.summary()
        assert s["pixel_bytes_per_object"] == 768.0
        assert s["pixel_cached_objects"] == 1

    def test_prewarm_charges_real_bytes(self, vae):
        cfg = StoreConfig(n_nodes=1, cache_bytes_per_node=1e5,
                          image_bytes=3072.0, latent_bytes=6e2)
        box = LatentBox.engine(vae=vae, config=cfg)
        box.put(4, recipe=Recipe(seed=4, height=16, width=16), prewarm=True)
        assert box.stat(4).pixel_bytes == 768.0


class TestUint8PutRoundTrip:
    def test_put_accepts_uint8_pixels(self, vae):
        """Pixels served by a get() (uint8) can be put back directly."""
        cfg = StoreConfig(n_nodes=1, cache_bytes_per_node=1e5,
                          image_bytes=768.0, latent_bytes=6e2)
        box = LatentBox.engine(vae=vae, config=cfg)
        box.put(1, recipe=Recipe(seed=9, height=16, width=16))
        img = box.get(1).payload
        assert img.dtype == np.uint8
        box.put(2, image=img)
        again = box.get(2).payload
        lsb = np.abs(again.astype(np.int16) - img.astype(np.int16))
        # encode -> decode round trip is lossy; just sanity-bound it
        assert again.dtype == np.uint8 and lsb.mean() < 64
