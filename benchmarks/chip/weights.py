"""The benchmark's weights: drawn from the seed on the device in one jitted
call, in the dtype they are served in, in the program's parameter layout.

The program is handed these weights and the reference reads the same
arrays, so neither takes weights that the other made. Values are drawn per
leaf from a key folded from the leaf's path:

- matrices: normal, scaled by one over the root of their fan-in;
- embedding and output head: normal with standard deviation 0.02;
- norm scales: uniform on [0.8, 1.2], so that a norm whose scale is
  dropped shows in the comparison.

The rules by leaf name (which axes a matrix contracts over, which leaves
are norm scales and which embeddings) are the family's
(``families/<family>.py``: ``FAN_IN_AXES``, ``NORMS``, ``EMBEDS``). A leaf
whose name is in none of them is an error: a new layout needs its rule
there before it can be measured.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    return str(path[-1].key)


def _draw(key, name: str, shape, dtype, stacked: bool, rules):
    if name in rules.NORMS:
        return jax.random.uniform(key, shape, jnp.float32, 0.8, 1.2
                                  ).astype(dtype)
    if name in rules.EMBEDS:
        return (jax.random.normal(key, shape, jnp.float32) * 0.02
                ).astype(dtype)
    if name in rules.FAN_IN_AXES:
        dims = shape[1:] if stacked else shape
        fan_in = int(np.prod([dims[a] for a in rules.FAN_IN_AXES[name]]))
        return (jax.random.normal(key, shape, jnp.float32)
                / np.sqrt(fan_in)).astype(dtype)
    raise KeyError(f"no weight rule for parameter {name!r} in "
                   f"{rules.__file__}")


def make_weights(specs, seed: int, rules):
    """Weights shaped as ``specs`` (the program's parameter shapes, e.g.
    ``jax.eval_shape(model.init, key)``), on the default device, drawn by
    the rules of the family module ``rules``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)

    def gen(key):
        out = []
        for path, s in flat:
            where = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, zlib.crc32(where.encode()) & 0x7FFFFFFF)
            out.append(_draw(k, _leaf_name(path), s.shape, s.dtype,
                             "layers" in where, rules))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(gen)(seed_key(seed))
