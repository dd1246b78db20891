"""Random weights made on the device from ``--seed``, in one jitted call.

Each model's tree comes from the ``init(key, sizes, head)`` of the
configuration's plain reference (``bench/references/<name>.py``), in the
layout the system under test consumes and the type it serves them in
(float32 masters; the system casts to its compute type itself).  The
reference rebuilds the same trees from the same seed with this module,
so it takes no array that the system made.
"""
from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key that keeps all 64 bits of a large ``seed``."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make(models: Dict[str, Dict], heads: Dict[str, str], seed: int,
         init: Callable):
    """Trees for every named model, in one jitted call on the default
    device: ``models`` maps a role to its sizes, ``heads`` a role to its
    head kind, and ``init(key, sizes, head)`` builds one tree."""
    roles = sorted(models)

    def build(key):
        keys = jax.random.split(key, len(roles))
        return {r: init(k, models[r], heads[r])
                for r, k in zip(roles, keys)}

    return jax.jit(build)(seed_key(seed))


def leaves(tree) -> Dict[str, jnp.ndarray]:
    """Leaves by a readable path, e.g. ``segments/0/0/attn/wq``."""
    out = {}
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out[name] = x
    return out
