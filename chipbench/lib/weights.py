"""The decoder's weights and the stored latents, made from ``--seed``.

Weights are made on the device in one jitted call, at fp32 (the precision
the configurations state), in the layout of the AutoencoderKL decoder
(``chipbench/references/autoencoderkl.py``): the benchmark hands the same
tree to the system under test and to the reference.  Convolutions and
dense layers draw N(0, 1 / fan_in), biases and GroupNorm affines are small
and non-zero so that every parameter shows in the output, and
``conv_out`` is scaled by ``out_gain`` so that served images sit inside
the display range instead of saturating it (a trained decoder's outputs
do).  Latents are seeded standard normals in fp16, one stream per object.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import numpy as np


def subseed(seed: int, tag: str) -> int:
    """A 31-bit seed derived from ``seed`` (any size) and a purpose tag."""
    words = [int(seed) >> s & 0xFFFFFFFF for s in (0, 32, 64)]
    words += [ord(ch) for ch in tag]
    return int(np.random.SeedSequence(words).generate_state(1)[0] >> 1)


def _tree(key, arch: Dict[str, Any], w: Dict[str, float]):
    """One normal draw for the whole tree, sliced into its leaves."""
    import jax
    import jax.numpy as jnp

    leaves = []                       # (shape, std, mean) in tree order

    def normal(shape, std, mean=0.0):
        leaves.append((shape, std, mean))
        return len(leaves) - 1

    def conv(k, cin, cout, gain=1.0):
        return {"w": normal((k, k, cin, cout), gain / np.sqrt(k * k * cin)),
                "b": normal((cout,), w["bias_std"])}

    def gn(c):
        return {"scale": normal((c,), w["norm_affine_std"], 1.0),
                "bias": normal((c,), w["norm_affine_std"])}

    def dense(cin, cout):
        return {"w": normal((cin, cout), 1.0 / np.sqrt(cin)),
                "b": normal((cout,), w["bias_std"])}

    def resblock(cin, cout):
        p = {"norm1": gn(cin), "conv1": conv(3, cin, cout),
             "norm2": gn(cout), "conv2": conv(3, cout, cout)}
        if cin != cout:
            p["shortcut"] = conv(1, cin, cout)
        return p

    chs = list(arch["block_out_channels"])
    top = chs[-1]
    tree = {"conv_in": conv(3, arch["latent_channels"], top),
            "mid": {"res1": resblock(top, top),
                    "attn": {"norm": gn(top), "q": dense(top, top),
                             "k": dense(top, top), "v": dense(top, top),
                             "proj": dense(top, top)},
                    "res2": resblock(top, top)},
            "up": []}
    cin = top
    for i, cout in enumerate(reversed(chs)):
        blocks = []
        for _ in range(arch["layers_per_block"] + 1):
            blocks.append(resblock(cin, cout))
            cin = cout
        level = {"blocks": blocks}
        if i < len(chs) - 1:
            level["upsample"] = {"conv": conv(3, cout, cout)}
        tree["up"].append(level)
    tree["norm_out"] = gn(chs[0])
    tree["conv_out"] = conv(3, chs[0], arch["out_channels"],
                            gain=w["out_gain"])

    sizes = [int(np.prod(shape)) for shape, _, _ in leaves]
    flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
    offsets = np.cumsum([0] + sizes)
    arrays = [mean + std * flat[o:o + n].reshape(shape)
              for (shape, std, mean), o, n in zip(leaves, offsets, sizes)]
    return jax.tree_util.tree_map(lambda i: arrays[i], tree)


@functools.lru_cache(maxsize=None)
def _maker(arch_items: Tuple, w_items: Tuple):
    import jax
    return jax.jit(lambda key: _tree(key, dict(arch_items), dict(w_items)))


def freeze(d: Dict[str, Any]) -> Tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))


def decoder_weights(arch: Dict[str, Any], init: Dict[str, float],
                    seed: int, device=None):
    """The fp32 decoder tree for ``seed``, made on ``device`` in one
    jitted call."""
    import jax
    key = jax.random.PRNGKey(subseed(seed, "weights"))
    if device is not None:
        key = jax.device_put(key, device)
    return _maker(freeze(arch), freeze(init))(key)


def latent(seed: int, oid: int, shape: Tuple[int, int, int]) -> np.ndarray:
    """The stored latent of object ``oid``: seeded N(0, 1) in fp16."""
    rng = np.random.default_rng([subseed(seed, "latents"), int(oid)])
    return rng.standard_normal(shape, np.float32).astype(np.float16)
