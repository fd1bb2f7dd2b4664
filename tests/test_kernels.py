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
    (1, 8, 13, 16, 16, 4), (2, 2, 10, 8, 8, 2),
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
    (1, 32, 32, 64, 8), (3, 4, 4, 32, 8), (1, 8, 13, 16, 4),
    (2, 2, 10, 8, 2),
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
    (1, 5, 7, 4, 4), (1, 9, 16, 32, 16), (1, 8, 13, 16, 16),
    (2, 2, 10, 8, 8),
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


def _banded(kernel, x, wt, b, rows, w_scale=None):
    """One banded conv op and its oracle (``w_scale`` dequantizes int8
    taps for the oracle)."""
    cin = x.shape[-1]
    wd = wt if w_scale is None else wt.astype(jnp.float32) * w_scale
    if kernel == "conv3x3":
        return (conv3x3(x, wt, b, rows=rows, interpret=True,
                        w_scale=w_scale), ref.conv3x3_ref(x, wd, b))
    s, gb = arr((cin,)), arr((cin,))
    fn, oracle = ((gn_silu_conv3x3, ref.gn_silu_conv3x3_ref)
                  if kernel == "gn_silu_conv3x3"
                  else (output_epilogue, ref.output_epilogue_ref))
    return (fn(x, s, gb, wt, b, groups=4, rows=rows, interpret=True,
               w_scale=w_scale), oracle(x, s, gb, wd, b, groups=4))


def _assert_close(kernel, out, want):
    if kernel == "output_epilogue":
        lsb = np.abs(np.asarray(out, np.int16) - np.asarray(want, np.int16))
        assert lsb.max() <= 1
    else:
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want, np.float32), atol=1e-4)


@pytest.mark.parametrize("kernel", ["conv3x3", "gn_silu_conv3x3",
                                    "output_epilogue"])
@pytest.mark.parametrize("h,rows,path", [
    (8, 8, "winograd_rows"), (12, 4, "winograd_rows"), (6, 2, "winograd_rows"),
    (9, 3, "direct"), (8, 1, "direct"),
])
def test_banded_conv_path(kernel, h, rows, path):
    """The band's height picks the path (F(2,3) along rows for an even
    band, the nine-tap loop for an odd one), and both match the oracle."""
    from repro.kernels.conv3x3 import plan
    cout = 3 if kernel == "output_epilogue" else 16
    x = arr((2, h, 10, 16))
    wt = arr((3, 3, 16, cout), scale=0.1)
    b = arr((cout,), scale=0.1)
    assert plan(x.shape, cout, 4, rows, 128,
                kernel != "conv3x3") == (rows, cout, path)
    _assert_close(kernel, *_banded(kernel, x, wt, b, rows))


@pytest.mark.parametrize("kernel", ["conv3x3", "gn_silu_conv3x3",
                                    "output_epilogue"])
@pytest.mark.parametrize("storage", ["int8", "bfloat16"])
def test_banded_conv_weight_storage(kernel, storage):
    """int8 taps with a per-channel ``w_scale`` and bf16 taps take the
    fp32 row transform and match the oracle on the dequantized filter."""
    cout = 3 if kernel == "output_epilogue" else 16
    x = arr((1, 8, 12, 16))
    b = arr((cout,), scale=0.1)
    w_scale = None
    if storage == "int8":
        wt = jnp.asarray(R.integers(-127, 128, (3, 3, 16, cout)), jnp.int8)
        w_scale = jnp.asarray(R.uniform(0.2, 1.0, (cout,)) / 1270,
                              jnp.float32)
    else:
        wt = arr((3, 3, 16, cout), jnp.bfloat16, scale=0.1)
    _assert_close(kernel, *_banded(kernel, x, wt, b, 8, w_scale))


def test_winograd_weights_reproduce_direct_rows():
    """F(2,3) along rows: the transformed rows t_k times the transformed
    filter U[k] give the direct three-row sums of both output rows."""
    from repro.kernels.conv3x3 import winograd_weights
    d = R.standard_normal((4, 5, 8))                  # band rows, W, Cin
    w = R.standard_normal((3, 3, 8, 6)).astype(np.float32)
    u = np.asarray(winograd_weights(jnp.asarray(w)), np.float64)
    assert u.shape == (4, 3, 8, 6)
    t = (d[0] - d[2], d[1] + d[2], d[2] - d[1], d[1] - d[3])
    for dx in range(3):
        m = [t[k] @ u[k, dx] for k in range(4)]
        y0 = sum(d[dy] @ w[dy, dx] for dy in range(3))
        y1 = sum(d[dy + 1] @ w[dy, dx] for dy in range(3))
        np.testing.assert_allclose(m[0] + m[1] + m[2], y0, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(m[1] - m[2] - m[3], y1, rtol=1e-5,
                                   atol=1e-5)


# -- the DC-AE ResBlock's fused epilogues (act="silu"; rms= with residual=)

def _epilogue_case(epilogue, h, cout, wt, w_scale=None, rows=8):
    """The fused conv and its oracle, ``cout`` output channels in tiles of
    128 (one, two or four of them)."""
    x = arr((2, h, 6, cout))
    b = arr((cout,), scale=0.1) if epilogue == "silu" else None
    kw = ({"act": "silu"} if epilogue == "silu" else
          {"rms": (1.0 + arr((cout,), scale=0.1), arr((cout,), scale=0.1)),
           "residual": x})
    wd = wt if w_scale is None else wt.astype(jnp.float32) * w_scale
    out = conv3x3(x, wt, b, rows=rows, block_cout=128, interpret=True,
                  w_scale=w_scale, **kw)
    return out, ref.conv3x3_epilogue_ref(x, wd, b, **kw)


@pytest.mark.parametrize("epilogue", ["silu", "rms_residual"])
@pytest.mark.parametrize("cout", [128, 256, 512])
@pytest.mark.parametrize("h,rows,path", [(8, 8, "winograd_rows"),
                                         (9, 3, "direct")])
def test_conv3x3_fused_epilogue(epilogue, cout, h, rows, path):
    """SiLU after the bias, and RMSNorm over every output channel plus
    the residual, on one, two and four Cout tiles and on both paths."""
    from repro.kernels.conv3x3 import plan
    assert plan((2, h, 6, cout), cout, 4, rows, 128, False,
                epilogue == "rms_residual") == (rows, 128, path)
    wt = arr((3, 3, cout, cout), scale=1 / np.sqrt(9 * cout))
    out, want = _epilogue_case(epilogue, h, cout, wt, rows=rows)
    assert out.shape == want.shape == (2, h, 6, cout)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("epilogue", ["silu", "rms_residual"])
def test_conv3x3_fused_epilogue_int8(epilogue):
    """int8 taps: the per-channel scale applies to the accumulator before
    the bias, then the epilogue."""
    cout = 256
    wt = jnp.asarray(R.integers(-127, 128, (3, 3, cout, cout)), jnp.int8)
    w_scale = jnp.asarray(R.uniform(0.2, 1.0, (cout,)) / (127 * 48),
                          jnp.float32)
    out, want = _epilogue_case(epilogue, 8, cout, wt, w_scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("hw,c,rows,conv1_rows", [(1024, 128, 8, 8),
                                                  (512, 256, 8, 16),
                                                  (256, 512, 4, 16)])
def test_rms_epilogue_plans_even_bands(hw, c, rows, conv1_rows):
    """The full-Cout output block and the residual block count against the
    unchanged VMEM budget, so the DC-AE ResBlock conv2s take 8, 8 and 4
    rows (all even: F(2,3)); conv1, with its one-tile output block, keeps
    the plain conv's band."""
    from repro.kernels.conv3x3 import VMEM_BUDGET, plan, step_vmem_bytes
    assert plan((4, hw, hw, c), c, 4, 32, 128, False, True) == (
        rows, 128, "winograd_rows")
    assert step_vmem_bytes(rows, hw, c, 4, tc=128, taps=12,
                           rms_cout=c) <= VMEM_BUDGET
    assert step_vmem_bytes(2 * rows, hw, c, 4, tc=128, taps=12,
                           rms_cout=c) > VMEM_BUDGET
    assert plan((4, hw, hw, c), c, 4, 32, 128, False) == (
        conv1_rows, 128, "winograd_rows")


def test_fused_epilogue_refuses_bad_options():
    x, wt = arr((1, 4, 4, 8)), arr((3, 3, 8, 8))
    with pytest.raises(ValueError, match="act"):
        conv3x3(x, wt, act="relu", interpret=True)
    with pytest.raises(ValueError, match="residual"):
        conv3x3(x, wt, rms=(arr((8,)), arr((8,))), interpret=True)
