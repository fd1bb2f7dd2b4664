"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in kernels/ref.py (assignment deliverable c)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.conv3x3 import conv3x3
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gn_silu import group_norm_silu
from repro.kernels.gn_silu_conv import gn_silu_conv3x3
from repro.kernels.output_epilogue import output_epilogue
from repro.kernels.rwkv6_scan import rwkv6_scan
from repro.kernels.upsample_conv import upsample_conv3x3

R = np.random.default_rng(0)


def arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(R.standard_normal(shape) * scale, dtype)


def tol(dtype):
    return 2e-5 if dtype == jnp.float32 else 6e-2


@pytest.mark.parametrize("shape,groups", [
    ((1, 8, 8, 64), 8), ((2, 16, 16, 128), 32), ((1, 7, 9, 32), 4),
    ((3, 4, 4, 256), 32), ((1, 1, 1, 16), 2),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gn_silu(shape, groups, dtype):
    x = arr(shape, dtype)
    s = arr(shape[-1:], dtype)
    b = arr(shape[-1:], dtype)
    out = group_norm_silu(x, s, b, groups=groups, interpret=True)
    want = ref.group_norm_silu_ref(x, s, b, groups=groups)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("n,h,w,cin,cout,groups", [
    (1, 8, 8, 16, 32, 4), (2, 16, 12, 8, 8, 2), (1, 32, 32, 64, 128, 8),
    (1, 5, 7, 4, 4, 2), (3, 4, 4, 32, 16, 8), (1, 16, 16, 64, 64, 32),
])
def test_gn_silu_conv3x3(n, h, w, cin, cout, groups):
    """Fused GN+SiLU+conv3x3 (res-block hot path) vs composed oracles."""
    x = arr((n, h, w, cin))
    s = arr((cin,))
    gb = arr((cin,))
    wt = arr((3, 3, cin, cout), scale=0.1)
    b = arr((cout,))
    out = gn_silu_conv3x3(x, s, gb, wt, b, groups=groups, rows=8,
                          interpret=True)
    want = ref.gn_silu_conv3x3_ref(x, s, gb, wt, b, groups=groups)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def test_band_rows_divides_h_under_vmem_pressure():
    """The VMEM halving must land on a divisor of h (e.g. h=18 would
    otherwise shrink 18 -> 9 -> 4, and 4 does not divide 18), and the
    whole per-step footprint — double-buffered band, normalized-band
    scratch, weights, output block — must fit the budget."""
    from repro.kernels.conv3x3 import (VMEM_BUDGET, band_rows,
                                       step_vmem_bytes)
    for h in (18, 24, 7, 5, 96):
        for width, cin in ((1024, 384), (16, 8), (4096, 512)):
            for fused_gn in (False, True):
                r = band_rows(h, width, cin, 4, 32, fused_gn=fused_gn)
                assert h % r == 0
                assert r == 1 or step_vmem_bytes(
                    r, width, cin, 4, fused_gn=fused_gn) <= VMEM_BUDGET


def test_conv3x3_non_power_of_two_height_vmem_fallback():
    """End-to-end at a height whose halvings aren't all divisors."""
    x = arr((1, 18, 12, 8))
    wt = arr((3, 3, 8, 8), scale=0.1)
    b = arr((8,))
    out = conv3x3(x, wt, b, rows=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.conv3x3_ref(x, wt, b)),
                               atol=1e-4)


def test_gn_silu_conv3x3_no_bias():
    x = arr((1, 8, 8, 8))
    s = arr((8,))
    gb = arr((8,))
    wt = arr((3, 3, 8, 8), scale=0.1)
    out = gn_silu_conv3x3(x, s, gb, wt, groups=2, interpret=True)
    want = ref.gn_silu_conv3x3_ref(x, s, gb, wt, groups=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def test_gn_silu_conv3x3_bf16():
    x = arr((1, 8, 8, 32), jnp.bfloat16)
    s = arr((32,), jnp.bfloat16)
    gb = arr((32,), jnp.bfloat16)
    wt = arr((3, 3, 32, 32), jnp.bfloat16, scale=0.1)
    b = arr((32,), jnp.bfloat16)
    out = gn_silu_conv3x3(x, s, gb, wt, b, groups=8, interpret=True)
    want = ref.gn_silu_conv3x3_ref(x, s, gb, wt, b, groups=8)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(jnp.bfloat16), rtol=tol(jnp.bfloat16))


@pytest.mark.parametrize("n,h,w,cin,cout", [
    (1, 8, 8, 16, 32), (2, 16, 12, 8, 8), (1, 32, 32, 64, 128),
    (1, 5, 7, 4, 4), (1, 1, 1, 8, 8), (3, 4, 4, 32, 16),
])
def test_upsample_conv3x3(n, h, w, cin, cout):
    """Fused nearest-2x upsample + conv (phase-decomposed) vs the
    upsample-then-conv oracle — the 4x intermediate never materializes."""
    x = arr((n, h, w, cin))
    wt = arr((3, 3, cin, cout), scale=0.1)
    b = arr((cout,))
    out = upsample_conv3x3(x, wt, b, rows=8, interpret=True)
    assert out.shape == (n, 2 * h, 2 * w, cout)
    want = ref.upsample_conv3x3_ref(x, wt, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def test_upsample_conv3x3_no_bias():
    x = arr((1, 8, 8, 8))
    wt = arr((3, 3, 8, 8), scale=0.1)
    out = upsample_conv3x3(x, wt, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.upsample_conv3x3_ref(x, wt)),
                               atol=1e-4)


def test_upsample_conv3x3_bf16():
    x = arr((1, 8, 8, 16), jnp.bfloat16)
    wt = arr((3, 3, 16, 16), jnp.bfloat16, scale=0.1)
    b = arr((16,), jnp.bfloat16)
    out = upsample_conv3x3(x, wt, b, interpret=True)
    want = ref.upsample_conv3x3_ref(x, wt, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(jnp.bfloat16), rtol=tol(jnp.bfloat16))


def test_upsample_conv3x3_matches_unfused_decode_path():
    """The fused op must agree with what the decoder used to compute:
    jnp.repeat upsample followed by the conv3x3 kernel."""
    x = arr((1, 6, 6, 8))
    wt = arr((3, 3, 8, 8), scale=0.1)
    b = arr((8,))
    fused = upsample_conv3x3(x, wt, b, rows=4, interpret=True)
    x2 = jnp.repeat(jnp.repeat(x, 2, axis=1), 2, axis=2)
    unfused = conv3x3(x2, wt, b, rows=4, interpret=True)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               atol=1e-4)


@pytest.mark.parametrize("n,h,w,cin,groups", [
    (1, 8, 8, 16, 4), (2, 16, 12, 8, 2), (1, 5, 7, 4, 2),
    (1, 32, 32, 64, 8), (3, 4, 4, 32, 8),
])
def test_output_epilogue(n, h, w, cin, groups):
    """Fused GN+SiLU+conv_out+clamp+uint8 vs the composed oracle: any
    disagreement is at most the 1-LSB rounding boundary."""
    x = arr((n, h, w, cin))
    s = arr((cin,))
    gb = arr((cin,))
    wt = arr((3, 3, cin, 3), scale=0.1)
    b = arr((3,), scale=0.1)
    out = output_epilogue(x, s, gb, wt, b, groups=groups, rows=8,
                          interpret=True)
    assert out.dtype == jnp.uint8
    want = ref.output_epilogue_ref(x, s, gb, wt, b, groups=groups)
    lsb = np.abs(np.asarray(out, np.int16) - np.asarray(want, np.int16))
    assert lsb.max() <= 1


def test_output_epilogue_saturates():
    """Large pre-activations clamp to exactly 0 / 255, never wrap."""
    x = arr((1, 8, 8, 8), scale=5.0)
    s = arr((8,), scale=5.0)
    gb = arr((8,), scale=5.0)
    wt = arr((3, 3, 8, 3), scale=5.0)
    out = np.asarray(output_epilogue(x, s, gb, wt, groups=2, rows=8,
                                     interpret=True))
    want = np.asarray(ref.output_epilogue_ref(x, s, gb, wt, groups=2))
    assert set(np.unique(out)) <= set(np.unique(want)) | {0, 255}
    assert np.abs(out.astype(np.int16) - want.astype(np.int16)).max() <= 1


def test_quantize_u8_round_trip_anchors():
    """The display mapping hits the exact anchor bytes."""
    y = jnp.asarray([-2.0, -1.0, 0.0, 1.0, 2.0], jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ref.quantize_u8_ref(y)), [0, 0, 128, 255, 255])


@pytest.mark.parametrize("n,hq,hkv,sq,skv,d,causal,window", [
    (1, 1, 1, 64, 64, 32, False, None),
    (2, 4, 2, 128, 128, 64, True, None),
    (1, 8, 2, 128, 128, 32, True, 64),
    (1, 2, 1, 32, 96, 16, True, None),
    (1, 4, 4, 64, 64, 128, False, None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(n, hq, hkv, sq, skv, d, causal, window, dtype):
    q = arr((n, hq, sq, d), dtype)
    k = arr((n, hkv, skv, d), dtype)
    v = arr((n, hkv, skv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=32, block_kv=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol(dtype), rtol=tol(dtype))


@pytest.mark.parametrize("n,hq,hkv,S,d", [
    (2, 4, 2, 128, 32), (1, 8, 1, 512, 64), (3, 6, 3, 256, 16),
    (1, 16, 2, 64, 128),
])
def test_decode_attention(n, hq, hkv, S, d):
    q = arr((n, hq, d))
    kc = arr((n, hkv, S, d))
    vc = arr((n, hkv, S, d))
    lens = jnp.asarray(R.integers(1, S + 1, n), jnp.int32)
    out = decode_attention(q, kc, vc, lens, block_kv=64, interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("n,h,w,cin,cout", [
    (1, 8, 8, 16, 32), (2, 16, 12, 8, 8), (1, 32, 32, 64, 128),
    (1, 5, 7, 4, 4), (1, 9, 16, 32, 16),
])
def test_conv3x3(n, h, w, cin, cout):
    x = arr((n, h, w, cin))
    wt = arr((3, 3, cin, cout), scale=0.1)
    b = arr((cout,))
    out = conv3x3(x, wt, b, rows=8, interpret=True)
    want = ref.conv3x3_ref(x, wt, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


def test_conv3x3_no_bias():
    x = arr((1, 8, 8, 8))
    wt = arr((3, 3, 8, 8), scale=0.1)
    np.testing.assert_allclose(np.asarray(conv3x3(x, wt, interpret=True)),
                               np.asarray(ref.conv3x3_ref(x, wt)), atol=1e-4)


@pytest.mark.parametrize("n,h,t,d,chunk", [
    (1, 2, 32, 16, 16), (2, 4, 64, 32, 32), (1, 1, 48, 8, 8),
])
def test_rwkv6_scan(n, h, t, d, chunk):
    r = arr((n, h, t, d), scale=0.5)
    k = arr((n, h, t, d), scale=0.5)
    v = arr((n, h, t, d), scale=0.5)
    w = arr((n, h, t, d), scale=0.3) - 1.0
    u = arr((h, d), scale=0.3)
    out, sT = rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    want, sW = ref.rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sW), atol=3e-4)


def test_chunked_model_forms_match_ref():
    """The XLA chunked forms used by the models (ssm.py) match the
    sequential oracle too."""
    from repro.models.ssm import rwkv6_chunked
    n, h, t, d = 2, 3, 96, 16
    r, k, v = (arr((n, h, t, d), scale=0.5) for _ in range(3))
    w = arr((n, h, t, d), scale=0.5)
    u = arr((h, d), scale=0.3)
    s0 = jnp.zeros((n, h, d, d), jnp.float32)
    oc, sc = rwkv6_chunked(r, k, v, w, u, s0, chunk=32)
    orf, srf = ref.rwkv6_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(oc), np.asarray(orf), atol=3e-4)
    np.testing.assert_allclose(np.asarray(sc), np.asarray(srf), atol=3e-4)
