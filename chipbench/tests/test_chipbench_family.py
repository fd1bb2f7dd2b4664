"""What the benchmark reads from the AutoencoderKL family module, against
constants taken from the harness before that module held it: the weight
tree bit for bit, the decode's call list field for field and the latent's
shape, for both configurations."""

import dataclasses
import hashlib
import json
import os

import numpy as np
import pytest

from chipbench_paths import BENCH

from lib import flops
from lib.cells import load_module
from lib.weights import decoder_weights

SEED = 2 ** 35 + 17
#: config -> (SHA-256 of every weight leaf's bytes in tree order, number
#: of calls, FLOPs per image, SHA-256 of the calls' fields, latent shape)
PARENT = {
    "sd35_vae-1024": (
        "528ede53973ecb16dac8b04bddd56b926d73af2086a18d1c67faf484e73ef0a2",
        37, 8926015782912.0,
        "04a1b505d85fa37747d7ff1105ed386fa6d2288e4f49cb4441f0cd02747c76d6",
        (128, 128, 16)),
    "sd15_vae-512": (
        "5321581f028a2cfe02618dfc704b09d83e5ad5bc653e8205aece35f36c032905",
        37, 2127971745792.0,
        "4751aa03b01924092230a005f16a2aa980a26b58b613d1afcac43c77f3bc40c4",
        (64, 64, 4)),
}


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    family = load_module(os.path.join(BENCH, "references",
                                      f"{cfg['reference']}.py"),
                         f"reference_{cfg['reference']}")
    return cfg, family


@pytest.mark.parametrize("name", sorted(PARENT))
def test_weights_are_the_parents(name):
    import jax
    cfg, family = _config(name)
    tree = decoder_weights(family, cfg["architecture"], cfg["weights"], SEED)
    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        digest.update(np.asarray(leaf).tobytes())
    assert digest.hexdigest() == PARENT[name][0]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_calls_are_the_parents(name):
    cfg, family = _config(name)
    calls = family.calls(cfg["architecture"])
    fields = json.dumps([dataclasses.astuple(c) for c in calls])
    assert len(calls) == PARENT[name][1]
    assert flops.flops_per_image(calls) == PARENT[name][2]
    assert hashlib.sha256(fields.encode()).hexdigest() == PARENT[name][3]


@pytest.mark.parametrize("name", sorted(PARENT))
def test_latent_shape_is_the_parents(name):
    cfg, family = _config(name)
    assert family.latent_hwc(cfg["architecture"]) == PARENT[name][4]


def test_nested_lists_compare_and_key():
    """A family's architecture may hold lists of lists (DC-AE's published
    ``decoder_qkv_multiscales`` is ``[[], [], [], [5], [5], [5]]``): the
    makers' cache key takes them, and the program's nested tuples match
    them."""
    import run as R
    from lib.weights import freeze
    arch = {"qkv": [[], [5]], "chs": [128, 256], "scale": 0.41407}
    key = freeze(arch)
    assert hash(key) == hash(freeze(json.loads(json.dumps(arch))))
    assert dict(key) == {"chs": (128, 256), "qkv": ((), (5,)),
                         "scale": 0.41407}
    assert R._plain(((), (5,))) == arch["qkv"]
    assert R._plain((128, 256)) == arch["chs"] and R._plain(0.5) == 0.5
