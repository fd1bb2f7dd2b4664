"""The DC-AE family module (``chipbench/references/autoencoderdc.py``) and
its cell: the published configuration's weight tree and call list, the
program's decode against the plain reference at a tiny size, a whole CPU
run of a tiny DC-AE cell through ``run.py``, and the cell's metric
readers on event names shaped as a v5e trace gives them."""

import json
import os
import shutil
import types

import numpy as np
import pytest

from chipbench_paths import BENCH, DATA, REPO, SERVED_RPS

import run as R
from lib import flops
from lib.cells import load_cell, load_module
from lib.weights import decoder_weights, freeze, latent

DC = load_module(os.path.join(BENCH, "references", "autoencoderdc.py"),
                 "reference_autoencoderdc")
SEED = 2 ** 35 + 23
with open(os.path.join(BENCH, "configs", "dcae_f32c32-1024.json")) as _f:
    CONFIG = json.load(_f)
ARCH = CONFIG["architecture"]
with open(os.path.join(DATA, "dcae_tiny-32.json")) as _f:
    TINY_CONFIG = json.load(_f)


def _tiny_program_config():
    from repro.vae.dcae import EVIT, RES, DCAEConfig
    return DCAEConfig(name="dcae_tiny", latent_channels=4,
                      block_out_channels=(8, 16, 32),
                      block_types=(RES, RES, EVIT),
                      layers_per_block=(1, 1, 1),
                      qkv_multiscales=((), (), (5,)), attention_head_dim=8)


def test_published_weight_tree():
    """196 leaves, 159,223,171 parameters (about 159 M) at the published
    widths, counted from the shapes alone."""
    shapes = []
    DC.weight_tree(ARCH, CONFIG["weights"],
                   lambda shape, std, mean=0.0: shapes.append(shape))
    assert len(shapes) == 196
    assert sum(int(np.prod(s)) for s in shapes) == 159_223_171


def test_published_calls():
    """97 calls, 7,380.8 GFLOP per 1024² image: 5,566.9 in the ResBlocks'
    3x3 convs, 790.3 in the up blocks (phase-decomposed), 1,005.6 in XLA
    (1x1 projections, depthwise and grouped convs), 10.8 in the nine
    linear attentions and 7.2 in conv_out."""
    calls = DC.calls(ARCH)
    assert len(calls) == 97
    assert flops.flops_per_image(calls) == 7380769177600.0
    by = {}
    for c in calls:
        by[c.kernel] = by.get(c.kernel, 0.0) + c.flops
    assert round(by["conv3x3"] / 1e9, 1) == 5566.9
    assert round(by["upsample_conv3x3"] / 1e9, 1) == 790.3
    assert round(by["xla"] / 1e9, 1) == 1005.6
    assert round(by["linear_attention"] / 1e9, 1) == 10.8
    assert round(by["rms_output_epilogue"] / 1e9, 1) == 7.2
    attn = [c for c in calls if c.kernel == "linear_attention"]
    assert len(attn) == 9
    # q, k and v read once, the output written once: 192 + 64 MiB per
    # block at 128x128x512
    assert attn[-1].h == 128 and attn[-1].act_bytes == 256 * 2 ** 20
    assert DC.latent_hwc(ARCH) == (32, 32, 32)


def test_program_keys_match_the_published_config():
    from repro.vae.model import CONFIGS
    cfg = CONFIGS[CONFIG["decoder"]]
    for ours, theirs in DC.PROGRAM_KEYS.items():
        assert R._plain(getattr(cfg, ours)) == ARCH[theirs], ours
    assert cfg.spatial_factor == 32


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_program_matches_reference(impl):
    """The program's decode_u8 against the plain fp32 reference on the
    benchmark's seeded weights.  ±1 LSB: the two sum the same products in
    different orders (banded and F(2,3) convs, tiled linear attention),
    so fp32 rounding can move a pixel across a rounding boundary."""
    import jax
    from repro.vae.model import VAE
    arch = TINY_CONFIG["architecture"]
    weights = decoder_weights(DC, arch, TINY_CONFIG["weights"], SEED)
    z = np.stack([latent(SEED, oid, DC.latent_hwc(arch)).astype(np.float32)
                  for oid in range(3)])
    want = np.asarray(DC.compiled(freeze(arch), "float32")(weights, z))
    vae = VAE(_tiny_program_config(), seed=0, with_encoder=False, impl=impl)
    vae.decoder = weights
    vae.set_weight_dtype("float32")
    got = np.asarray(vae.decode_u8(jax.numpy.asarray(z)))
    assert got.shape == want.shape == (3, 32, 32, 3)
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


@pytest.fixture
def dcae_root(tmp_path, monkeypatch):
    """A checkout with the benchmark as it is plus, as new files only, the
    tiny DC-AE configuration, a tiny scan mix and a cell ``dcae_tiny.scan``
    that reports what ``dcae-1024.scan`` reports; the program knows the
    tiny decoder for the test's duration."""
    from repro.vae.model import CONFIGS
    monkeypatch.setitem(CONFIGS, "dcae_tiny", _tiny_program_config())
    root = tmp_path
    bench = root / "chipbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (bench / "tests" / "data").mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "dcae_tiny-32.json"),
                bench / "tests" / "data")
    shutil.copy(os.path.join(DATA, "tiny_scan.json"), bench / "mixes")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "dcae_tiny-32", "source": "test",
                         "file": "chipbench/tests/data/dcae_tiny-32.json",
                         "reduced": [], "why": "CPU tests"})
    b["workloads"].append({"name": "dcae_tiny.scan", "config": "dcae_tiny-32",
                           "traffic": "tiny_scan", "chips": 1,
                           "why": "CPU tests"})
    for m in b["end_to_end"] + b["per_layer"]:
        w = m.get("workloads")
        if w and "dcae-1024.scan" in w:
            w.append("dcae_tiny.scan")
    if not any("dcae_tiny.scan" in m.get("workloads", ())
               for m in b["end_to_end"]):
        b["end_to_end"].append(SERVED_RPS | {"workloads": ["dcae_tiny.scan"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return str(root)


def _run(root, trace=0, **kw):
    args = R.parse(["--workload", "dcae_tiny.scan", "--seed", str(SEED),
                    "--seconds", "0.5", "--trace", str(trace)])
    return R.run(args, load_cell("dcae_tiny.scan", root), require_tpu=False,
                 compile_cache=False, **kw)


def test_cell_resolves_the_family():
    cell = load_cell("dcae-1024.scan", REPO)
    assert cell.config["decoder"] == "dcae_f32c32"
    assert [m["name"] for m in cell.end_to_end] == ["served_rps", "setup_s"]
    assert [m["name"] for m in cell.per_layer] == [
        "decode_mfu.dcae", "conv_roofline.dcae", "linattn_roofline.dcae",
        "idle_share.dcae"]
    assert cell.reference().PROGRAM_KEYS == DC.PROGRAM_KEYS


def test_tiny_cell_runs_correct(dcae_root):
    res = _run(dcae_root)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"served_rps", "setup_s"}
    res = _run(dcae_root, trace=1)
    assert res["correct"] is True, res["checks"]
    # a CPU trace has no TPU plane: the device metrics read nothing
    assert res["metrics"] == {}


def test_tiny_cell_control_is_not_correct(dcae_root):
    res = _run(dcae_root, precision="bfloat16")
    assert res["correct"] is False
    assert res["checks"]["mismatch_ppm"]["value"] > \
        res["checks"]["mismatch_ppm"]["limit"]


# -- the metric readers, on event names as a v5e trace of bucket 2 gives them

def _event(name, result, operands, seconds):
    text = (f"%{name}.7 = {result}{{3,2,1,0:T(8,128)}} custom-call("
            + ", ".join(f"{s}{{3,2,1,0}} %a.{i}"
                        for i, s in enumerate(operands))
            + '), custom_call_target="tpu_custom_call"')
    return types.SimpleNamespace(name=text, seconds=seconds)


EVENTS = [
    # conv_in: 32x32, 32 -> 1024, one band of 32 rows per image
    _event("conv3x3", "f32[2,32,32,1024]",
           ["f32[2,34,34,32]", "f32[4,3,32,1024]", "f32[1,1024]"], 2e-4),
    # a ResBlock conv at 256x256x512, 8-row bands
    _event("conv3x3", "f32[64,8,256,512]",
           ["f32[64,10,258,512]", "f32[4,3,512,512]", "f32[1,512]"], 0.03),
    # the up block 512 -> 256 from 256x256
    _event("upsample_conv3x3", "f32[32,16,2,256,512]",
           ["f32[32,18,258,512]", "f32[2,2,2,512,256]", "f32[1,256]"], 0.01),
    _event("rms_output_epilogue", "u8[64,32,1024,3]",
           ["f32[64,34,1026,128]", "f32[4,3,128,3]", "f32[1,3]"], 0.004),
    _event("linear_attention", "f32[2,16384,1024]",
           ["f32[2,16384,1536]"] * 6, 0.002),
]


class _Trace:
    def __init__(self, events):
        self.events = events

    def kernel_ops(self, kinds):
        from lib.profile import kind_of
        return [e for e in self.events if kind_of(e.name) in kinds]

    def module_executions(self, contains):
        hit = [e for e in self.events if contains(e)]
        return [(types.SimpleNamespace(seconds=0.5), hit)] if hit else []


def _ctx(events):
    from lib.peaks import PEAKS
    calls = DC.calls(ARCH)
    window = types.SimpleNamespace(calls=[types.SimpleNamespace(
        batcher={"decodes": 2})])
    return types.SimpleNamespace(
        trace=_Trace(events), peaks=PEAKS["TPU v5 lite"], calls=calls,
        flops_per_image=flops.flops_per_image(calls), window=window)


def _reader(name):
    return load_module(os.path.join(BENCH, "metrics", f"{name}.py"),
                       f"metric_{name}").read


def test_conv_roofline_matches_every_event():
    conv = _reader("conv_roofline.dcae")
    mod = load_module(os.path.join(BENCH, "metrics", "conv_roofline.dcae.py"),
                      "metric_conv_roofline_dcae")
    calls = DC.calls(ARCH)
    want = [("conv_in", 2), ("s2.b0.conv1", 2), ("s1.upsample", 2),
            ("conv_out", 2), ("s3.b0.attn", 2)]
    for e, (what, images) in zip(EVENTS, want):
        from lib.profile import kind_of
        call, n = mod.match(calls, kind_of(e.name), e.name)
        assert (call.what, n) == (what, images)
    ctx = _ctx(EVENTS)
    v = conv(ctx)
    least = sum(flops.least_seconds(mod.match(calls, k, e.name)[0], 2,
                                    197e12, 819e9)
                for e, k in zip(EVENTS[:4], ["conv3x3", "conv3x3",
                                             "upsample_conv3x3",
                                             "rms_output_epilogue"]))
    assert v == pytest.approx(100 * least / sum(e.seconds
                                                for e in EVENTS[:4]))
    assert 0 < v <= 100
    # an event no call matches makes the metric read nothing
    odd = _event("conv3x3", "f32[2,32,32,999]",
                 ["f32[2,34,34,32]", "f32[4,3,32,999]", "f32[1,999]"], 1e-4)
    assert conv(_ctx(EVENTS + [odd])) is None


def test_linattn_roofline_and_decode_mfu():
    ctx = _ctx(EVENTS)
    call = next(c for c in ctx.calls if c.kernel == "linear_attention"
                and c.h == 128)
    want = 100 * max(2 * call.flops / 197e12,
                     2 * call.act_bytes / 819e9) / 0.002
    assert _reader("linattn_roofline.dcae")(ctx) == pytest.approx(want)
    # bytes bound it: 2 x 256 MiB over 819 GB/s
    assert 2 * call.act_bytes / 819e9 > 2 * call.flops / 197e12
    assert _reader("decode_mfu.dcae")(ctx) == pytest.approx(
        100 * 2 * ctx.flops_per_image / (0.5 * 197e12))
    assert _reader("linattn_roofline.dcae")(_ctx(EVENTS[:4])) is None
    none = types.SimpleNamespace(trace=None, peaks=None)
    for name in ("decode_mfu.dcae", "conv_roofline.dcae",
                 "linattn_roofline.dcae", "idle_share.dcae"):
        assert _reader(name)(none) is None
