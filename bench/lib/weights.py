"""Random weights made on the device from ``--seed``, in one jitted call.

The trees have the layout the system under test consumes (a decoder with
its layers stacked on a leading axis) and the type it serves them in
(float32 masters; the system casts to its compute type itself).  The
reference rebuilds the same trees from the same seed with this module,
so it takes no array that the system made.

Matrices are normal with standard deviation ``1/sqrt(fan_in)``; norm
weights are ones.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key that keeps all 64 bits of a large ``seed``."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(
        jnp.float32(fan_in))


def decoder(key, m: Dict, head: str) -> Dict:
    """One decoder's tree; ``head`` is ``"lm"`` (vocabulary logits) or
    ``"value"`` (a scalar per position: critic and reward models)."""
    L, D, H, KV, hd, F, V = (m["n_layers"], m["d_model"], m["n_heads"],
                             m["n_kv_heads"], m["head_dim"], m["d_ff"],
                             m["vocab_size"])
    ks = iter(jax.random.split(key, 10))
    layer = {
        "ln1": jnp.ones((L, D), jnp.float32),
        "attn": {"wq": _normal(next(ks), (L, D, H * hd), D),
                 "wk": _normal(next(ks), (L, D, KV * hd), D),
                 "wv": _normal(next(ks), (L, D, KV * hd), D),
                 "wo": _normal(next(ks), (L, H * hd, D), H * hd)},
        "ln2": jnp.ones((L, D), jnp.float32),
        "mlp": {"w_gate": _normal(next(ks), (L, D, F), D),
                "w_up": _normal(next(ks), (L, D, F), D),
                "w_down": _normal(next(ks), (L, F, D), F)},
    }
    tree = {"embed": _normal(next(ks), (V, D), V),
            "segments": ((layer,),),
            "final_norm": jnp.ones((D,), jnp.float32)}
    if head == "lm":
        tree["lm_head"] = _normal(next(ks), (D, V), D)
    else:
        tree["v_head"] = _normal(next(ks), (D, 1), D)
    return tree


def make(models: Dict[str, Dict], heads: Dict[str, str], seed: int):
    """Trees for every named model, in one jitted call on the default
    device: ``models`` maps a role to its sizes, ``heads`` a role to its
    head kind."""
    roles = sorted(models)

    def build(key):
        keys = jax.random.split(key, len(roles))
        return {r: decoder(k, models[r], heads[r])
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
