"""The DC-AE decoder family (``repro.vae.dcae``) and its kernels, on the
CPU at small sizes: the linear-attention kernel and the RMSNorm output
path against their oracles in interpret mode, the up block's channel
shortcut against its index formula, the part-major regrouping, and a
tiny DC-AE served through the engine's normal path."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import linear_attention as la
from repro.kernels import ops, ref
from repro.vae import dcae
from repro.vae.dcae import EVIT, RES, DCAEConfig
from repro.vae.model import CONFIGS, VAE

#: Both block kinds, one block per stage, two heads of 8 at the top.
TINY = DCAEConfig(name="dcae_tiny", latent_channels=4,
                  block_out_channels=(8, 16, 32),
                  block_types=(RES, RES, EVIT), layers_per_block=(1, 1, 1),
                  qkv_multiscales=((), (), (5,)), attention_head_dim=8)


def _packed(key, n, tokens, cq, sources):
    keys = jax.random.split(jax.random.PRNGKey(key), sources)
    return [jax.random.normal(k, (n, tokens, 3 * cq)) for k in keys]


@pytest.mark.parametrize("n,tokens,cq,head_dim,sources,block", [
    (1, 2048, 256, 32, 2, 512),     # lane-aligned, whole tiles
    (2, 1500, 128, 32, 2, 512),     # uneven: a last partial token tile
    (1, 77, 24, 8, 1, 1024),        # narrow parts, one tile of 77 tokens
    (2, 300, 16, 8, 3, 128),        # narrow, three sources, partial tile
])
def test_linear_attention_matches_oracle(n, tokens, cq, head_dim, sources,
                                         block):
    xs = _packed(n + tokens, n, tokens, cq, sources)
    want = ref.linear_attention_ref(xs, head_dim)
    got = la.linear_attention(xs, head_dim=head_dim, block_tokens=block,
                              interpret=True)
    assert got.shape == (n, tokens, sources * cq)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_linear_attention_eps_and_relu_zeroed_rows():
    """A head whose k ReLU zeroes everywhere, and q rows that ReLU zeroes,
    divide 0 by eps: their outputs are exactly 0, never NaN."""
    n, tokens, cq, d = 1, 640, 128, 32
    x = np.array(_packed(7, n, tokens, cq, 1)[0])
    x[..., cq:cq + d] = -np.abs(x[..., cq:cq + d])       # head 0's k < 0
    x[:, ::3, :cq] = -np.abs(x[:, ::3, :cq])             # q rows < 0
    x = jnp.asarray(x)
    got = np.asarray(la.linear_attention([x], head_dim=d, block_tokens=256,
                                         interpret=True))
    want = np.asarray(ref.linear_attention_ref([x], d))
    assert np.isfinite(got).all()
    assert (got[..., :d] == 0).all()
    assert (got[:, ::3] == 0).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_linear_attention_counts_call_sites():
    xs = _packed(3, 1, 64, 16, 2)
    before = la.TRACED["linear_attention"]
    jax.jit(lambda a, b: ops.linear_attention(
        [a, b], 8, impl="pallas_interpret")).lower(*xs)
    assert la.TRACED["linear_attention"] == before + 1
    ops.linear_attention(xs, 8, impl="xla")               # oracle: not counted
    assert la.TRACED["linear_attention"] == before + 1


def test_rms_output_epilogue_matches_oracle():
    k = jax.random.split(jax.random.PRNGKey(4), 5)
    x = 2.0 * jax.random.normal(k[0], (2, 16, 16, 32))
    scale = 1.0 + 0.1 * jax.random.normal(k[1], (32,))
    bias = 0.1 * jax.random.normal(k[2], (32,))
    w = 0.1 * jax.random.normal(k[3], (3, 3, 32, 3))
    b = 0.02 * jax.random.normal(k[4], (3,))
    want = np.asarray(ref.rms_output_epilogue_ref(x, scale, bias, w, b))
    got = np.asarray(ops.rms_output_epilogue(x, scale, bias, w, b,
                                             impl="pallas_interpret"))
    assert got.dtype == np.uint8 and got.shape == (2, 16, 16, 3)
    # the banded conv's F(2,3) sum differs from the lax conv by fp32
    # rounding alone, which can move a pixel across a rounding boundary
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


@pytest.mark.parametrize("cin,cout", [(16, 8), (8, 8)])     # r = 2 and 4
def test_channel_shortcut_index_formula(cin, cout):
    r = 4 * cout // cin
    x = np.random.default_rng(cin).standard_normal((2, 3, 5, cin))
    got = np.asarray(dcae.channel_shortcut(jnp.asarray(x), cout))
    want = np.empty((2, 6, 10, cout))
    for c in range(cout):
        for i in range(2):
            for j in range(2):
                want[:, i::2, j::2, c] = x[..., (4 * c + 2 * i + j) // r]
    np.testing.assert_array_equal(got, want.astype(got.dtype))


def test_part_major_regroups_heads():
    """Diffusers' heads own 3d consecutive channels [q | k | v]; the packed
    source holds every q, then every k, then every v."""
    d, heads = 4, 3
    perm = dcae.part_major(3 * d * heads, d)
    for h in range(heads):
        for part in range(3):
            got = perm[part * heads * d + h * d:part * heads * d + h * d + d]
            assert list(got) == list(range(h * 3 * d + part * d,
                                           h * 3 * d + part * d + d))


def test_published_config():
    cfg = CONFIGS["dcae_f32c32"]
    assert cfg.spatial_factor == 32
    assert cfg.latent_shape(1024) == (32, 32, 32)
    assert cfg.block_out_channels == (128, 256, 512, 512, 1024, 1024)
    assert cfg.qkv_multiscales == ((), (), (), (5,), (5,), (5,))


@pytest.mark.parametrize("change", [
    {"norm_type": "batch_norm"}, {"upsample_block_type": "pixel_shuffle"},
    {"layers_per_block": (1, 1)}, {"block_types": (RES, RES, "Other")},
    {"attention_head_dim": 12}])
def test_config_refuses_what_the_program_lacks(change):
    import dataclasses
    with pytest.raises(ValueError):
        dataclasses.replace(TINY, **change)


def test_kl_walkers_refuse_dcae():
    from repro.kernels.autotune import decode_shapes
    from repro.vae.serve import (decoder_bytes_per_image,
                                 decoder_flops_per_image)
    cfg = CONFIGS["dcae_f32c32"]
    for walk in (decoder_flops_per_image, decoder_bytes_per_image):
        with pytest.raises(ValueError, match="dcae_f32c32"):
            walk(cfg, 1024)
    with pytest.raises(ValueError, match="dcae_f32c32"):
        decode_shapes(cfg, (32, 32, 32), 1)


@pytest.fixture(scope="module")
def tiny_vae():
    return VAE(TINY, seed=3, impl="pallas_interpret")


def test_decode_u8_matches_float_decode(tiny_vae):
    """The uint8 path is the float decode quantized (±1 LSB: the fused
    output conv's sum against the lax conv's)."""
    z = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 8, 4))
    u8 = np.asarray(tiny_vae.decode_u8(z))
    f = np.asarray(ref.quantize_u8_ref(tiny_vae.decode(z)))
    assert u8.shape == (2, 32, 32, 3)
    assert np.abs(u8.astype(int) - f.astype(int)).max() <= 1
    assert tiny_vae.encoder is None
    with pytest.raises(ValueError, match="no encoder"):
        tiny_vae.encode_mean(np.zeros((1, 32, 32, 3), np.float32))


def test_served_through_the_engine(tiny_vae):
    """The normal read path: LatentBox.engine -> get_many -> ServingEngine
    -> DecodeBatcher -> VAE.decode_u8, at the family's latent shape."""
    from repro.store import LatentBox, StoreConfig
    hwc = TINY.latent_shape(32)
    rng = np.random.default_rng(0)
    lat = {oid: rng.standard_normal(hwc).astype(np.float16)
           for oid in range(5)}
    cfg = StoreConfig(n_nodes=1, cache_bytes_per_node=0.0,
                      image_bytes=32 * 32 * 3.0, latent_bytes=512.0,
                      decode_buckets=(1, 2))
    box = LatentBox.engine(vae=tiny_vae, config=cfg)
    for oid, z in lat.items():
        box.put(oid, latent=z)
    box.backend.engine.prewarm_decode(hwc)
    res = box.get_many([0, 1, 2, 3, 4])
    for r in res:
        want = np.asarray(tiny_vae.decode_u8(
            lat[r.oid].astype(np.float32)[None]))[0]
        assert r.payload.dtype == np.uint8
        assert np.abs(r.payload.astype(int) - want.astype(int)).max() <= 1


def test_warm_up_span_counts_linear_attention(monkeypatch):
    """The tiny DC-AE's warm-up span: six banded convs (conv_in, two in
    each of two ResBlocks, the output path) and one linear-attention call
    (its one EfficientViT block)."""
    from repro.serve import engine
    opened = []

    class Span:
        def __init__(self, name, **stats):
            opened.append((name, dict(stats)))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **stats):
            opened[-1][1].update(stats)

    monkeypatch.setattr(engine, "TraceAnnotation", Span)
    vae = VAE(TINY, seed=0, impl="pallas_interpret")
    engine.DecodeBatcher(vae, buckets=(1,)).prewarm(TINY.latent_shape(32))
    (stats,) = [s for name, s in opened if name == "lb.warm_up"]
    assert stats["linear_attention"] == 1
    assert stats["winograd_rows"] + stats["direct"] == 6


# -- the ResBlock as two banded convs with fused epilogues

def _res_params(key, c):
    k = jax.random.split(jax.random.PRNGKey(key), 5)
    return {"conv1": {"w": jax.random.normal(k[0], (3, 3, c, c))
                      / np.sqrt(9 * c),
                      "b": 0.1 * jax.random.normal(k[1], (c,))},
            "conv2": {"w": jax.random.normal(k[2], (3, 3, c, c))
                      / np.sqrt(9 * c)},
            "norm": {"scale": 1.0 + 0.1 * jax.random.normal(k[3], (c,)),
                     "bias": 0.1 * jax.random.normal(k[4], (c,))}}


@pytest.mark.parametrize("c", [16, 256])                 # one and two tiles
def test_res_block_fused_matches_xla(c):
    """``x + rms_norm(conv2(silu(conv1(x))))``: the two fused banded convs
    against the XLA composition, which is the decode's unfused form."""
    p = _res_params(5, c)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 8, 8, c))
    want = dcae.res_block(x, p, impl="xla")
    unfused = x + dcae.rms_norm(
        ref.conv3x3_ref(dcae.silu(ref.conv3x3_ref(x, p["conv1"]["w"],
                                                  p["conv1"]["b"])),
                        p["conv2"]["w"]), p["norm"])
    np.testing.assert_array_equal(np.asarray(want), np.asarray(unfused))
    got = dcae.res_block(x, p, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


def test_tiny_decode_u8_pallas_matches_xla(tiny_vae):
    """The tiny DC-AE's uint8 decode on the kernels (fused ResBlocks)
    against the XLA oracle path on the same weights: ±1 LSB."""
    xla = VAE(TINY, seed=3, impl="xla")
    z = jax.random.normal(jax.random.PRNGKey(7), (2, 8, 8, 4))
    got = np.asarray(tiny_vae.decode_u8(z))
    want = np.asarray(xla.decode_u8(z))
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


#: The published block layout (three ResBlock stages, three EfficientViT
#: stages, three blocks each) at narrow widths: a 128² image.
LAYOUT = DCAEConfig(name="dcae_layout", latent_channels=4,
                    block_out_channels=(8, 8, 8, 16, 16, 16),
                    attention_head_dim=8)


def _warm_up_stats(monkeypatch, vae, latent_hwc):
    from repro.serve import engine
    opened = []

    class Span:
        def __init__(self, name, **stats):
            opened.append((name, dict(stats)))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        def set_metadata(self, **stats):
            opened[-1][1].update(stats)

    monkeypatch.setattr(engine, "TraceAnnotation", Span)
    engine.DecodeBatcher(vae, buckets=(1,)).prewarm(latent_hwc)
    (stats,) = [s for name, s in opened if name == "lb.warm_up"]
    return stats


def test_warm_up_counts_fused_epilogues(monkeypatch):
    """A DC-AE decode of the published layout traces 9 SiLU and 9 RMSNorm
    + residual epilogues (one of each per ResBlock), 20 banded convs, all
    on F(2,3), and 9 linear attentions; a KL decode no fused epilogue."""
    from repro.vae.model import VAEConfig
    stats = _warm_up_stats(monkeypatch,
                           VAE(LAYOUT, seed=0, impl="pallas_interpret"),
                           LAYOUT.latent_shape(128))
    assert {k: stats[k] for k in ("silu", "rms_residual", "winograd_rows",
                                  "direct", "linear_attention")} == {
        "silu": 9, "rms_residual": 9, "winograd_rows": 20, "direct": 0,
        "linear_attention": 9}
    kl = VAEConfig(name="tiny_kl", latent_channels=4,
                   block_out_channels=(16, 32), layers_per_block=1, groups=4)
    stats = _warm_up_stats(monkeypatch,
                           VAE(kl, seed=0, impl="pallas_interpret"),
                           (4, 4, 4))
    assert stats["silu"] == stats["rms_residual"] == 0
    assert stats["winograd_rows"] + stats["direct"] > 0
