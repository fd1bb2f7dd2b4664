"""The trace reduction, on a small trace recorded on the CPU (the host's
XLA executor threads stand in for the device), and the parsing of the
device's HLO event names."""

import glob
import time

import pytest

import chipbench_paths  # noqa: F401  (puts the harness on sys.path)

from lib import flops, profile, readers


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    d = str(tmp_path_factory.mktemp("trace"))
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    profile.start(d)
    with TraceAnnotation("window"):
        for _ in range(3):
            with TraceAnnotation("get_many"):
                f(x).block_until_ready()
            with TraceAnnotation("await_arrival"):
                time.sleep(0.02)
    profile.stop()
    assert glob.glob(d + "/plugins/profile/*/*.xplane.pb")
    return profile.reduce_dir(
        d, device_plane=lambda n: n == "/host:CPU",
        op_line=lambda n: n.startswith("tf_XLAPjRtCpuClient"),
        module_line=lambda n: False)


def test_busy_union_and_gaps(cpu_trace):
    t = cpu_trace
    assert t is not None
    assert t.window_s >= 0.06
    assert 0 < t.busy_s < t.window_s
    gaps = t.idle_gaps()
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    # the three sleeps are the longest idle stretches, and the host was
    # waiting for arrivals in each
    assert [g[0] for g in gaps[:3]] == ["await_arrival"] * 3
    assert all(g[1] >= 0.015 for g in gaps[:3])
    assert sum(g[1] for g in gaps) + t.busy_s == pytest.approx(t.window_s,
                                                               rel=1e-6)
    bd = t.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert sum(v for _, v in t.seconds_by_kind().items()) >= t.busy_s * 0.999


def test_no_window_span_gives_nothing():
    class PD:
        planes = []
    assert profile.reduce(PD()) is None


def test_merge_and_clip():
    assert profile.merge([(3, 5), (0, 2), (1, 4), (7, 8)]) == [(0, 5), (7, 8)]
    assert profile.clip([(0, 5), (7, 8)], 1, 7.5) == [(1, 5), (7, 7.5)]


SD35 = {"block_out_channels": [128, 256, 512, 512], "layers_per_block": 2,
        "latent_channels": 16, "resolution": 1024, "out_channels": 3}
SD15 = dict(SD35, latent_channels=4, resolution=512)
# event names as a v5e trace of the decode at bucket 2 gives them (the
# upsampler's from SD1.5 at 512 px)
CONV = ('%gn_silu_conv3x3.29 = f32[16,16,128,512]{3,2,1,0:T(8,128)} '
        'custom-call(f32[16,18,130,512]{3,2,1,0:T(8,128)} %bitcast.2956, '
        'f32[2,1,512]{2,1,0:T(1,128)S(1)} %broadcast_in_dim_reshape.68, '
        'f32[2,1,512]{2,1,0:T(1,128)S(1)} %b.1, f32[1,512]{1,0} %b.2, '
        'f32[3,3,512,512]{3,2,1,0:T(8,128)S(1)} %copy-done.68, f32[1,512]'
        '{1,0:T(1,128)S(1)} %bitcast.3065), custom_call_target='
        '"tpu_custom_call", frontend_attributes={kernel_metadata={}}')
UPS = ('%upsample_conv3x3.5 = f32[32,16,2,256,512]{4,3,2,1,0:T(8,128)} '
       'custom-call(f32[32,18,258,256]{3,2,1,0:T(8,128)} %bitcast.489, '
       'f32[2,2,2,2,256,256]{5,4,3,2,1,0:T(8,128)S(1)} %custom-call.50, '
       'f32[1,256]{1,0} %b), custom_call_target="tpu_custom_call"')
OUT = ('%output_epilogue.3 = u8[128,16,1024,3]{3,2,1,0:T(8,128)(4,1)} '
       'custom-call(f32[128,18,1026,128]{3,2,1,0:T(8,128)} %bitcast.970, '
       'f32[2,1,128]{2,1,0:T(1,128)S(1)} %bitcast.3010), '
       'custom_call_target="tpu_custom_call"')


def test_kernel_events_match_decoder_calls():
    calls = flops.decoder_calls(SD35)
    assert profile.kind_of(CONV) == "gn_silu_conv3x3"
    assert profile.kind_of("%fusion.12 = f32[2] fusion(f32[2] %a)") == "fusion"
    assert profile.kind_of("%copy-start = (f32[2]) copy-start()") == \
        "copy-start"
    call, n = readers._match(calls, "gn_silu_conv3x3", CONV)
    assert (call.h, call.cin, call.cout, n) == (128, 512, 512, 2)
    call, n = readers._match(flops.decoder_calls(SD15), "upsample_conv3x3",
                             UPS)
    assert (call.h, call.cin, call.cout, n) == (256, 256, 256, 2)
    assert call.what == "up2.upsample"
    assert readers._match(calls, "upsample_conv3x3", UPS) is None
    call, n = readers._match(calls, "output_epilogue", OUT)
    assert (call.what, n) == ("conv_out", 2)


STATS = ('%gn_silu_conv3x3.6 = (f32[2,1,256]{2,1,0:T(1,128)S(1)}, '
         'f32[2,1,256]{2,1,0:T(1,128)S(1)}) custom-call(f32[2,262144,256]'
         '{2,1,0:T(8,128)} %reshape.43), custom_call_target="tpu_custom_call"')


def test_roofline_never_passes_the_least_time():
    """A kernel event that took exactly its least time reads 100%."""
    import types
    calls = flops.decoder_calls(SD35)
    call, n = readers._match(calls, "gn_silu_conv3x3", CONV)
    least = flops.least_seconds(call, n, 197e12, 819e9)
    op = profile.Op(CONV, 0.0, least * 1e9)
    stats = profile.Op(STATS, least * 1e9, least * 2e9)   # not a conv
    trace = profile.TraceSummary(0.0, 1e9, [[op, stats]], [[]], [])
    ctx = types.SimpleNamespace(trace=trace, calls=calls, peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert readers.conv_roofline(ctx) == pytest.approx(100.0)
    ctx.trace = profile.TraceSummary(0.0, 1e9, [[profile.Op(
        "%conv3x3.3 = f32[4] custom-call()", 0, 5)]],
        [[]], [])
    assert readers.conv_roofline(ctx) is None
