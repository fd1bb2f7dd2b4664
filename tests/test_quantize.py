"""Quantized decoder storage behind the ±1-LSB uint8 serving gate:
bf16 passes on a calibrated decoder (every bucket, padded slots
included), grid-snapped int8 round-trips to 0 LSB, an out-of-tolerance
quantizer is rejected at engine open, and quantized pixels survive a
flush + reopen bit-identical."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ops
from repro.store import LatentBox, StoreConfig
from repro.vae import quantize as Q
from repro.vae.model import VAE, DEMO_VAE, calibrated_vae

LATENT_HWC = (8, 8, 4)
BUCKETS = (1, 2, 4, 8)


@pytest.fixture(scope="module")
def vae_bf16():
    return calibrated_vae("demo", seed=0, weight_dtype="bfloat16")


@pytest.fixture(scope="module")
def vae_int8_snapped():
    vae = calibrated_vae("demo", seed=0)
    Q.snap_to_grid(vae)
    vae.set_weight_dtype("int8")
    return vae


def store_config(**kw):
    base = dict(n_nodes=1, cache_bytes_per_node=1e5, adaptive=False,
                decode_buckets=BUCKETS)
    base.update(kw)
    return StoreConfig(**base)


# ---------------------------------------------------------------------------
# array-level quantizers
# ---------------------------------------------------------------------------

class TestQuantizeInt8:
    def test_per_channel_scale_shape_and_range(self, rng):
        w = jnp.asarray(rng.standard_normal((3, 3, 8, 16)), jnp.float32)
        qw = Q.quantize_int8(w)
        assert qw.q.dtype == jnp.int8 and qw.q.shape == w.shape
        assert qw.scale.shape == (16,) and qw.scale.dtype == jnp.float32
        assert int(jnp.max(jnp.abs(qw.q.astype(jnp.int32)))) <= 127
        # per-channel: each channel's max |q| saturates at exactly 127
        assert int(jnp.min(jnp.max(jnp.abs(qw.q.astype(jnp.int32)),
                                   axis=(0, 1, 2)))) == 127

    def test_grid_snap_roundtrips_exactly(self, rng):
        w = jnp.asarray(rng.standard_normal((3, 3, 4, 8)), jnp.float32)
        snapped = Q.quantize_int8(w).dequant(jnp.float32)
        again = Q.quantize_int8(snapped).dequant(jnp.float32)
        np.testing.assert_array_equal(np.asarray(snapped), np.asarray(again))

    def test_zero_channel_gets_unit_scale(self):
        w = jnp.zeros((3, 3, 2, 2), jnp.float32)
        qw = Q.quantize_int8(w)
        np.testing.assert_array_equal(np.asarray(qw.scale), 1.0)
        np.testing.assert_array_equal(np.asarray(qw.q), 0)

    def test_unknown_weight_dtype_rejected(self):
        with pytest.raises(ValueError, match="weight_dtype"):
            Q.quantize_decoder({}, "int4")


class TestDecoderStorage:
    def test_bytes_per_param_ladder(self):
        vae = VAE(DEMO_VAE, seed=0, with_encoder=False)
        f32 = Q.decoder_storage(vae.decoder)
        bf16 = Q.decoder_storage(Q.quantize_decoder(vae.decoder, "bfloat16"))
        int8 = Q.decoder_storage(Q.quantize_decoder(vae.decoder, "int8"))
        assert f32["bytes_per_param"] == pytest.approx(4.0)
        assert 1.9 < bf16["bytes_per_param"] < 2.2       # 1-D affine stays f32
        assert 1.0 < int8["bytes_per_param"] < 1.3       # denses stay bf16
        assert f32["params"] == bf16["params"] == int8["params"]

    def test_float32_is_identity(self):
        vae = VAE(DEMO_VAE, seed=0, with_encoder=False)
        assert Q.quantize_decoder(vae.decoder, "float32") is vae.decoder


# ---------------------------------------------------------------------------
# the ±1-LSB gate
# ---------------------------------------------------------------------------

class TestGate:
    def test_bf16_within_one_lsb_every_bucket(self, vae_bf16):
        lsb = Q.check_u8_gate(vae_bf16, BUCKETS, LATENT_HWC)
        assert set(lsb) == set(BUCKETS)
        assert max(lsb.values()) <= 1

    def test_snapped_int8_is_exact(self, vae_int8_snapped):
        lsb = Q.check_u8_gate(vae_int8_snapped, BUCKETS, LATENT_HWC)
        assert max(lsb.values()) == 0

    def test_raw_int8_random_decoder_rejected(self):
        """Unsnapped int8 on this decoder drifts past 1 LSB — the gate's
        whole point is that it, not a promise, decides admissibility."""
        vae = calibrated_vae("demo", seed=0)
        vae.set_weight_dtype("int8")
        with pytest.raises(Q.QuantizationGateError, match="int8"):
            Q.check_u8_gate(vae, (1, 2), LATENT_HWC)

    def test_float32_override_is_the_oracle(self, vae_bf16):
        """precision='float32' must bypass quantized weights entirely."""
        z = Q.probe_latents(LATENT_HWC, 2, seed=3)
        oracle = VAE(DEMO_VAE, seed=0, with_encoder=False)
        oracle.decoder = vae_bf16.decoder
        oracle.set_weight_dtype("float32")
        ref = np.asarray(oracle.decode_u8(jnp.asarray(z)))
        got = np.asarray(vae_bf16.decode_u8(jnp.asarray(z),
                                            precision="float32"))
        np.testing.assert_array_equal(ref, got)


# ---------------------------------------------------------------------------
# engine integration: open-time gate + padded slots + persistence
# ---------------------------------------------------------------------------

def _put_latents(box, n, rng):
    for oid in range(n):
        box.put(oid, latent=rng.standard_normal(LATENT_HWC)
                .astype(np.float16))


class TestEngineGate:
    def test_open_accepts_bf16_and_reports_gate(self, vae_bf16, rng):
        box = LatentBox.engine(vae=vae_bf16,
                               config=store_config(weight_dtype="bfloat16"))
        _put_latents(box, 3, rng)
        assert all(r.payload.dtype == np.uint8 for r in box.get_many([0, 1]))
        s = box.summary()
        assert s["weight_dtype"] == "bfloat16"
        assert max(s["quantize_gate_lsb"].values()) <= 1

    @pytest.mark.parametrize("n", [3, 5])
    def test_padded_windows_match_oracle(self, vae_bf16, rng, n):
        """Windows of 3 and 5 pad buckets 4 and 8: quantized serving must
        stay within ±1 LSB of the f32 oracle on the *real* slots."""
        box = LatentBox.engine(vae=vae_bf16,
                               config=store_config(weight_dtype="bfloat16"))
        lat = [rng.standard_normal(LATENT_HWC).astype(np.float16)
               for _ in range(n)]
        for oid, z in enumerate(lat):
            box.put(oid, latent=z)
        got = box.get_many(list(range(n)))
        for r, z in zip(got, lat):
            zb = jnp.asarray(np.asarray(z, np.float32)[None])
            ref = np.asarray(vae_bf16.decode_u8(zb, precision="float32"))[0]
            err = np.abs(ref.astype(np.int16)
                         - r.payload.astype(np.int16)).max()
            assert err <= 1

    def test_out_of_tolerance_quantizer_rejected(self, vae_bf16,
                                                 monkeypatch):
        """The gate is the admission contract: a quantizer whose output
        drifts (here: weights zeroed) must fail the open, loudly."""
        monkeypatch.setitem(
            Q.QUANTIZERS, "bfloat16",
            lambda params: Q._map_weights(
                params, lambda p: (p * 0 if getattr(p, "ndim", 0) >= 2
                                   else p)))
        vae = calibrated_vae("demo", seed=0)
        with pytest.raises(Q.QuantizationGateError):
            LatentBox.engine(vae=vae,
                             config=store_config(weight_dtype="bfloat16",
                                                 decode_buckets=(1, 2)))

    def test_raw_int8_rejected_at_open(self):
        vae = calibrated_vae("demo", seed=0)
        with pytest.raises(Q.QuantizationGateError):
            LatentBox.engine(vae=vae,
                             config=store_config(weight_dtype="int8",
                                                 decode_buckets=(1, 2)))

    def test_quantization_requires_uint8_pixels(self, vae_bf16):
        with pytest.raises(ValueError, match="uint8 fast path"):
            LatentBox.engine(vae=vae_bf16,
                             config=store_config(weight_dtype="bfloat16",
                                                 pixel_format="float32",
                                                 image_bytes=64e3))


class TestQuantizedPersistence:
    def test_pixels_identical_across_flush_and_reopen(self, tmp_path, rng):
        cfg = store_config(weight_dtype="bfloat16", decode_buckets=(1, 2))
        with LatentBox.open(tmp_path / "box", config=cfg) as box:
            _put_latents(box, 4, rng)
            box.flush()
            before = [np.asarray(r.payload).copy()
                      for r in box.get_many([0, 1, 2, 3])]
        with LatentBox.open(tmp_path / "box", config=cfg) as box:
            after = [np.asarray(r.payload)
                     for r in box.get_many([0, 1, 2, 3])]
        for a, b in zip(before, after):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# kernel-level int8 parity (differential, interpret vs xla)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestInt8KernelParity:
    """The Pallas in-kernel dequant must match dequant-then-XLA — the
    scale fold into the f32 accumulator is exact per output channel."""

    def test_conv3x3(self, rng):
        x = jnp.asarray(rng.standard_normal((1, 8, 8, 8)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((3, 3, 8, 16)) / 8, jnp.float32)
        b = jnp.asarray(rng.standard_normal((16,)) * 0.01, jnp.float32)
        qw = Q.quantize_int8(w)
        got = ops.conv3x3(x, qw, b, impl="pallas_interpret")
        ref = ops.conv3x3(x, qw.dequant(jnp.float32), b, impl="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_upsample_conv3x3(self, rng):
        x = jnp.asarray(rng.standard_normal((1, 8, 8, 8)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((3, 3, 8, 8)) / 8, jnp.float32)
        b = jnp.asarray(rng.standard_normal((8,)) * 0.01, jnp.float32)
        qw = Q.quantize_int8(w)
        got = ops.upsample_conv3x3(x, qw, b, impl="pallas_interpret")
        ref = ops.upsample_conv3x3(x, qw.dequant(jnp.float32), b, impl="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)
