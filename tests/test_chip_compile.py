"""Compile checks of the decode kernels at the paper's SD3.5 widths, for a
described TPU v5e: the chip's own compiler refuses what interpret mode
accepts (unaligned layouts, shape casts Mosaic cannot lower, casts it does
not support, more VMEM than a kernel may use).  Nothing runs, so these say
nothing about results or times — the interpret-mode tests in
``test_kernels.py`` hold the numerics.

The topology is described inside a module-scoped fixture (never while a
module is imported), and the fixture skips where it cannot be described.
The persistent compilation cache is off around the compiles: a compile for
a described chip could be written but never read back here.
"""

import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.conv3x3 import conv3x3
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gn_silu_conv import gn_silu_conv3x3
from repro.kernels.output_epilogue import output_epilogue
from repro.kernels.upsample_conv import upsample_conv3x3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def compile_for(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_conv3x3_conv_in(one_chip):
    """The decoder's conv_in: 16 latent channels -> 512 at 128x128."""
    compile_for(one_chip, lambda x, w, b: conv3x3(x, w, b),
                (1, 128, 128, 16), (3, 3, 16, 512), (512,))


def test_gn_silu_conv3x3_1024_128(one_chip):
    """The top level's res-block conv at 1024x1024x128."""
    compile_for(one_chip,
                lambda x, s, g, w, b: gn_silu_conv3x3(x, s, g, w, b),
                (1, 1024, 1024, 128), (128,), (128,), (3, 3, 128, 128),
                (128,))


def test_gn_silu_conv3x3_128_512(one_chip):
    """The mid block's and lowest up level's res-block conv at
    128x128x512."""
    compile_for(one_chip,
                lambda x, s, g, w, b: gn_silu_conv3x3(x, s, g, w, b),
                (1, 128, 128, 512), (512,), (512,), (3, 3, 512, 512),
                (512,))


_SHAPE = re.compile(r"f32\[([0-9,]+)\]")


@pytest.mark.parametrize("hw,c,rows", [(1024, 128, 8), (128, 512, 16)])
def test_gn_silu_conv3x3_call_shapes(one_chip, hw, c, rows):
    """The compiled conv call keeps the shapes the benchmark's trace
    reader matches: operand 0 the row-banded input ``[N * bands, rows+2,
    W+2, Cin]``, the result's last axis ``Cout``; the F(2,3) filter
    ``[4, 3, Cin, Cout]`` follows the band."""
    compiled = compile_for(
        one_chip, lambda x, s, g, w, b: gn_silu_conv3x3(x, s, g, w, b),
        (1, hw, hw, c), (c,), (c,), (3, 3, c, c), (c,))
    calls = [ln for ln in compiled.as_text().splitlines()
             if re.match(r"\s*%gn_silu_conv3x3(\.\d+)? = f32\[", ln)]
    assert len(calls) == 1                 # the conv (stats return a tuple)
    shapes = [tuple(int(v) for v in m.split(","))
              for m in _SHAPE.findall(calls[0])]
    result = shapes[0]
    operands = [s for s in shapes[1:] if len(s) == 4]
    assert result[-1] == c
    assert operands[0] == (hw // rows, rows + 2, hw + 2, c)
    assert operands[1] == (4, 3, c, c)


def test_upsample_conv3x3_512_from_128(one_chip):
    """The first upsampler: 512 channels, 128x128 -> 256x256."""
    compile_for(one_chip, lambda x, w, b: upsample_conv3x3(x, w, b),
                (1, 128, 128, 512), (3, 3, 512, 512), (512,))


def test_output_epilogue_1024(one_chip):
    """GN + SiLU + conv_out 128 -> 3 + uint8 quantize at 1024x1024."""
    c = compile_for(one_chip,
                    lambda x, s, g, w, b: output_epilogue(x, s, g, w, b),
                    (1, 1024, 1024, 128), (128,), (128,), (3, 3, 128, 3),
                    (3,))
    assert c.out_info.dtype == jnp.uint8


def test_flash_attention_mid_block(one_chip):
    """The mid-block attention: one head over 128x128 = 16,384 tokens."""
    compile_for(one_chip, lambda q, k, v: flash_attention(q, k, v),
                (1, 1, 16384, 512), (1, 1, 16384, 512), (1, 1, 16384, 512))
