"""Exponential Moving Average collection (InstructGPT/DS-Chat optional
feature 1): a sharded shadow of the actor params updated every PPO step;
the EMA checkpoint is what ships."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import obs


def init(params):
    """A float32 copy of ``params`` (never the same arrays: a trainer
    may donate its params)."""
    return jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True),
                        params)


def _update(ema, params, decay: float = 0.992):
    return jax.tree.map(
        lambda e, p: decay * e + (1.0 - decay) * p.astype(jnp.float32),
        ema, params)


update = jax.jit(obs.named("ema_update", _update))


def to_params(ema, like):
    return jax.tree.map(lambda e, p: e.astype(p.dtype), ema, like)
