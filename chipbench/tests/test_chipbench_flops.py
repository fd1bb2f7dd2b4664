"""The AutoencoderKL family's operation counts against closed forms
worked out by hand from the published architecture (not against the
program's own count)."""

import json
import os

import pytest

import chipbench_paths  # noqa: F401  (puts the harness on sys.path)

from lib import flops
from references import autoencoderkl as KL

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def closed_form(lat, res, c=(128, 256, 512, 512), layers=3):
    """Convs 2*H*W*Cin*Cout*k^2, upsamplers 16 collapsed taps on the
    pre-upsample grid, attention 4*N^2*C for QK^T and PV plus 4 dense
    projections 2*N*C^2 each."""
    h = res // 8
    f = 2 * h * h * lat * 512 * 9                      # conv_in
    f += 4 * 2 * h * h * 512 * 512 * 9                 # mid res blocks
    n = h * h
    f += 4 * n * n * 512 + 4 * 2 * n * 512 * 512       # mid attention
    cin = 512
    for i, cout in enumerate(reversed(c)):
        for _ in range(layers):
            f += 2 * h * h * (cin * cout + cout * cout) * 9
            if cin != cout:
                f += 2 * h * h * cin * cout
            cin = cout
        if i < 3:
            f += 2 * h * h * cout * cout * 16
            h *= 2
    return f + 2 * h * h * 128 * 3 * 9                 # conv_out


@pytest.mark.parametrize("name,gflop", [("sd35_vae-1024", 8926.0158),
                                        ("sd15_vae-512", 2127.9718)])
def test_flops_per_image(name, gflop):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        arch = json.load(f)["architecture"]
    got = flops.flops_per_image(KL.calls(arch))
    assert got == pytest.approx(closed_form(arch["latent_channels"],
                                            arch["resolution"]), rel=1e-12)
    assert got / 1e9 == pytest.approx(gflop, abs=1e-3)


def test_attention_counted_once():
    """9,475.8 GFLOP (the program's estimate for SD3.5 at 1024 px) counts
    QK^T and PV twice over; the difference is exactly 4*N^2*C."""
    with open(os.path.join(CONFIGS, "sd35_vae-1024.json")) as f:
        arch = json.load(f)["architecture"]
    n = 128 * 128
    assert (flops.flops_per_image(KL.calls(arch)) + 4 * n * n * 512) / 1e9 \
        == pytest.approx(9475.7716, abs=1e-3)


def test_least_time_is_the_larger_bound():
    call = KL.calls({"block_out_channels": [128, 256, 512, 512],
                     "layers_per_block": 2, "latent_channels": 16,
                     "resolution": 1024, "out_channels": 3})[-1]
    assert call.what == "conv_out"
    t = flops.least_seconds(call, 2, 197e12, 819e9)
    assert t == max(call.flops * 2 / 197e12, call.bytes_for(2) / 819e9)
    assert call.bytes_for(2) == 2 * call.act_bytes + call.weight_bytes
