"""The decoder's weights and the stored latents, made from ``--seed``.

Weights are made on the device in one jitted call, at fp32 (the precision
the configurations state): the decoder family's reference module lays out
the tree (``weight_tree(arch, init, normal)``, calling ``normal(shape,
std, mean=0.0)`` once per leaf in tree order), and this module fills it
from one normal draw of the tree's whole size, sliced leaf by leaf in that
order.  The benchmark hands the same tree to the system under test and to
the reference.  ``init`` is the configuration's ``weights`` group, whose
stds the family reads.  Latents are seeded standard normals in fp16, one
stream per object.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Tuple

import numpy as np


def subseed(seed: int, tag: str) -> int:
    """A 31-bit seed derived from ``seed`` (any size) and a purpose tag."""
    words = [int(seed) >> s & 0xFFFFFFFF for s in (0, 32, 64)]
    words += [ord(ch) for ch in tag]
    return int(np.random.SeedSequence(words).generate_state(1)[0] >> 1)


def _tree(key, weight_tree: Callable, arch: Dict[str, Any],
          init: Dict[str, float]):
    """One normal draw for the whole tree, sliced into its leaves."""
    import jax
    import jax.numpy as jnp

    leaves = []                       # (shape, std, mean) in tree order

    def normal(shape, std, mean=0.0):
        leaves.append((shape, std, mean))
        return len(leaves) - 1

    tree = weight_tree(arch, init, normal)
    sizes = [int(np.prod(shape)) for shape, _, _ in leaves]
    flat = jax.random.normal(key, (sum(sizes),), jnp.float32)
    offsets = np.cumsum([0] + sizes)
    arrays = [mean + std * flat[o:o + n].reshape(shape)
              for (shape, std, mean), o, n in zip(leaves, offsets, sizes)]
    return jax.tree_util.tree_map(lambda i: arrays[i], tree)


@functools.lru_cache(maxsize=None)
def _maker(weight_tree: Callable, arch_items: Tuple, w_items: Tuple):
    import jax
    return jax.jit(lambda key: _tree(key, weight_tree, dict(arch_items),
                                     dict(w_items)))


def _frozen(v):
    return tuple(_frozen(x) for x in v) if isinstance(v, list) else v


def freeze(d: Dict[str, Any]) -> Tuple:
    """``d``'s items, sorted, with lists (nested ones too) as tuples: a
    key for the caches of jitted makers."""
    return tuple(sorted((k, _frozen(v)) for k, v in d.items()))


def decoder_weights(family, arch: Dict[str, Any], init: Dict[str, float],
                    seed: int, device=None):
    """The fp32 decoder tree of ``family`` (the configuration's reference
    module) for ``seed``, made on ``device`` in one jitted call."""
    import jax
    key = jax.random.PRNGKey(subseed(seed, "weights"))
    if device is not None:
        key = jax.device_put(key, device)
    return _maker(family.weight_tree, freeze(arch), freeze(init))(key)


def latent(seed: int, oid: int, shape: Tuple[int, int, int]) -> np.ndarray:
    """The stored latent of object ``oid``: seeded N(0, 1) in fp16."""
    rng = np.random.default_rng([subseed(seed, "latents"), int(oid)])
    return rng.standard_normal(shape, np.float32).astype(np.float16)
