"""Random weights drawn on the device from the run's seed, in one program.

The tree and the distributions are the configuration's reference
module's (``param_specs``); the dtype is the one the configuration is
served in. Each leaf is drawn from the seed folded with its index, so
the same seed gives the same weights, and no float32 copy of a whole
stacked weight is ever live beside the rest.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, *salt: int) -> jax.Array:
    """A raw threefry key from a seed of any size (and optional salt)."""
    state = np.random.SeedSequence([int(seed), *salt]).generate_state(2)
    return jnp.asarray(state, jnp.uint32)


def _leaves(specs: dict, path: tuple = ()):
    for name in sorted(specs):
        node = specs[name]
        if isinstance(node, dict):
            yield from _leaves(node, path + (name,))
        else:
            yield path + (name,), node


def draw(specs: dict, seed: int, dtype: Any) -> dict:
    """Weights for ``specs`` (leaves ``(shape, mean, std)``) in ``dtype``."""
    leaves = list(_leaves(specs))

    def make(key: jax.Array) -> dict:
        out: dict = {}
        for i, (path, (shape, mean, std)) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            x = mean + std * jax.random.normal(k, shape, jnp.float32)
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = x.astype(dtype)
        return out

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))
