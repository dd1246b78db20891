"""Reward / critic models: transformer backbone + scalar value head.

Matches DeepSpeed-Chat's design: the reward model scores a (prompt,
response) pair with the value at the *last response token*; the critic
reuses the same structure and emits per-token values for PPO.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig
from repro.models import transformer as T
from repro.models.modules import ParamSpec, init_tree


def param_specs(cfg: ModelConfig) -> dict:
    specs = T.param_specs(cfg)
    specs.pop("lm_head", None)           # value head instead of LM head
    specs["v_head"] = ParamSpec((cfg.d_model, 1), ("embed", None))
    return specs


def init_params(cfg: ModelConfig, key) -> dict:
    return init_tree(param_specs(cfg), key, cfg.pdtype)


def values(cfg: ModelConfig, params, tokens, *, embeds=None,
           encoder_embeds=None, moe_counts=False):
    """Per-token scalar values: (B, L); with ``moe_counts`` also the
    forward's MoE counts (:func:`repro.models.transformer.forward`)."""
    hidden, _, _, counts = T.forward(cfg, params, tokens=tokens,
                                     embeds=embeds,
                                     encoder_embeds=encoder_embeds,
                                     mode="full", moe_counts=True)
    v = (hidden @ params["v_head"]).astype(jnp.float32)[..., 0]
    return (v, counts) if moe_counts else v


def end_scores(cfg: ModelConfig, params, tokens, attn_mask,
               moe_counts=False):
    """Score at the last non-pad token of each sequence: (B,)."""
    v, counts = values(cfg, params, tokens, moe_counts=True)
    last = jnp.maximum(attn_mask.sum(-1) - 1, 0).astype(jnp.int32)
    s = jnp.take_along_axis(v, last[:, None], axis=1)[:, 0]
    return (s, counts) if moe_counts else s


def frozen(cfg: ModelConfig, params) -> dict:
    """A frozen (never trained) model's tree as its forward reads it: the
    layers and the LM head in the compute dtype (the cast
    :func:`repro.models.transformer.forward` makes at entry).  Kept as
    they are: the value head, which :func:`values` applies uncast, and
    the embedding table, which the forward gathers rows of and casts.
    (With the table cast too, the TPU compiler tiles the vocabulary
    projection of the scoring program worse, for the actor's forward as
    well as the reference's: scoring the 3-layer OPT-1.3B/350M pair took
    11 ms longer on a v5e.)"""
    out = T.cast_params(cfg, params)
    for k in ("v_head", "embed"):
        if k in params:
            out[k] = params[k]
    return out


def pairwise_loss(cfg: ModelConfig, params, chosen, rejected, chosen_mask,
                  rejected_mask):
    """DeepSpeed-Chat reward loss: -log sigmoid(r_chosen - r_rejected)."""
    rc = end_scores(cfg, params, chosen, chosen_mask)
    rr = end_scores(cfg, params, rejected, rejected_mask)
    loss = -jax.nn.log_sigmoid(rc - rr).mean()
    acc = (rc > rr).mean()
    return loss, acc
