"""Seeded weights in the program's parameter layout, and the same values again
one layer at a time for the references.

Every entry is a small integer times a power of two: the sum of four 5-bit
fields of one random 32-bit word, minus their mean 62, times ``2**-10``.  That
is an integer in [-62, 62] with mean 0 (std about 18.5, so std 0.018 after
scaling, near the published ``initializer_range`` of 0.02), exact in bfloat16
and in float32, so the program's bf16 weights and the reference's float32
weights are the same numbers whatever the compiler fuses.  The mean must be 0:
a bias of even 2/1024 per entry adds up across the 9728-wide MLP into one
direction that swamps every token's own, and all positions then give the
same output.

The value of a leaf depends only on the seed, the leaf's path in the layout
(``stack/sub0/attn/wq``) and, for the per-layer stacks ``pre`` and ``stack``,
the layer's index in its stack: so the reference can make layer ``l`` alone.
"""
from __future__ import annotations

import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

SCALE = 2.0 ** -10
STACKS = ("pre", "stack")


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (wider than 32 bits too)."""
    k = jax.random.PRNGKey(seed % (1 << 32))
    return jax.random.fold_in(k, seed >> 32)


def _path_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def values(key, shape: Tuple[int, ...], dtype):
    """The weight entries for one key: exact small integers times 2**-10."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    s = sum(((bits >> (8 * i)) & 31).astype(jnp.int32) for i in range(4))
    return ((s - 62).astype(jnp.float32) * SCALE).astype(dtype)


def leaf(key, path: str, layer, shape, dtype=jnp.float32):
    """Leaf ``path`` of layer ``layer`` of its stack (shape without the layer
    axis; ``layer`` may be traced), or ``layer=None`` for a leaf outside the
    stacks, for the key ``seed_key(seed)``."""
    k = _path_key(key, path)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return values(k, tuple(shape), dtype)


def _paths(tree, prefix=()):
    if isinstance(tree, dict):
        for name, sub in tree.items():
            yield from _paths(sub, prefix + (name,))
    else:
        yield prefix, tree


def _set(tree: Dict, path, value):
    for name in path[:-1]:
        tree = tree.setdefault(name, {})
    tree[path[-1]] = value


def build(layout, seed: int):
    """The whole parameter pytree for ``layout`` (a pytree of
    ``ShapeDtypeStruct``, the program's own layout) in one jitted call, made
    on the device in each leaf's own dtype."""
    leaves = list(_paths(layout))

    def make(key):
        out: Dict = {}
        for path, sds in leaves:
            name = "/".join(path)
            k = _path_key(key, name)
            if path[0] in STACKS:
                n = sds.shape[0]
                v = jax.vmap(lambda i, k=k, sds=sds: values(
                    jax.random.fold_in(k, i), sds.shape[1:], sds.dtype))(
                        jnp.arange(n))
            else:
                v = values(k, sds.shape, sds.dtype)
            _set(out, path, v)
        return out

    return jax.jit(make)(seed_key(seed))
