"""Batched autoregressive generation: prefill + scanned decode.

This is the RLHF *experience generation* hot loop the paper identifies as
memory-bandwidth-bound — each step touches every weight once to emit one
token per sequence.  The Hybrid Engine runs this function under the TP
(inference) param layout.

Prompts are fixed-length per batch (the paper's own benchmark recipe:
256 prompt + 256 generated tokens); the cache is preallocated to
``prompt_len + max_new_tokens`` (the attention layer internally clamps it
to the sliding window and ring-buffers writes when one is configured).

``generate`` always scans the full ``max_new_tokens`` — after every
sequence has emitted EOS the remaining steps still run, forcing EOS out
of the sampler.  The serving-grade path with early-exit chunked decode
and continuous batching lives in :mod:`repro.serving.engine`; it reuses
:func:`decode_scan_step` so its token stream is bit-identical to this
reference implementation.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving.sampling import sample


def prefill(cfg: ModelConfig, params, tokens, cache, *, embeds=None,
            encoder_embeds=None, moe_counts=False):
    """Run the prompt through the model, filling ``cache``.
    Returns (last-position logits (B, V), cache), and with ``moe_counts``
    the forward's MoE counts (:func:`repro.models.transformer.forward`)."""
    hidden, cache, _, counts = T.forward(
        cfg, params, tokens=tokens, embeds=embeds,
        encoder_embeds=encoder_embeds, mode="prefill", cache=cache,
        moe_counts=True)
    logits = T.logits_fn(cfg, params, hidden[:, -1:])[:, 0]
    return (logits, cache, counts) if moe_counts else (logits, cache)


def decode_step(cfg: ModelConfig, params, token, cache, position, *,
                embeds=None, encoder_embeds=None, block_tables=None,
                moe_counts=False):
    """One decode step.  token: (B,) int32; position: (B,) absolute.
    Returns (logits (B, V), new_cache), and with ``moe_counts`` the
    step's MoE counts.  With ``block_tables`` set, ``cache`` is the paged
    block pool (see :func:`repro.models.transformer.init_paged_cache`)."""
    kw = {}
    if cfg.embed_inputs:
        kw["tokens"] = token[:, None]
    else:
        kw["embeds"] = embeds
    hidden, cache, _, counts = T.forward(
        cfg, params, mode="decode", cache=cache, positions=position[:, None],
        encoder_embeds=encoder_embeds, block_tables=block_tables,
        moe_counts=True, **kw)
    logits = T.logits_fn(cfg, params, hidden)[:, 0]
    return (logits, cache, counts) if moe_counts else (logits, cache)


def decode_scan_step(cfg: ModelConfig, params, *, temperature: float,
                     top_k: int, eos_id: Optional[int], top_p: float = 1.0,
                     encoder_embeds=None, moe_counts=False):
    """Build the ``lax.scan`` body shared by :func:`generate` and the
    chunked engine decode.

    Carry is ``(logits, cache, key, pos, done)``; the per-step output is
    ``(tok, was_done)`` where ``was_done`` is the *pre-step* done flag:
    the step that emits the first EOS still records ``was_done=False``
    (the EOS token itself counts as generated), every later step forces
    ``eos_id`` out of the sampler with ``was_done=True``.  With
    ``moe_counts`` the step's MoE counts follow as a third output.
    """
    def step(carry, _):
        logits, cache, key, pos, done = carry
        key, sub = jax.random.split(key)
        tok = sample(logits, sub, temperature=temperature, top_k=top_k,
                     top_p=top_p)
        if eos_id is not None:
            tok = jnp.where(done, eos_id, tok)
        logits, cache, counts = decode_step(cfg, params, tok, cache, pos,
                                            encoder_embeds=encoder_embeds,
                                            moe_counts=True)
        new_done = done | (tok == eos_id) if eos_id is not None else done
        ys = (tok, done, counts) if moe_counts else (tok, done)
        return (logits, cache, key, pos + 1, new_done), ys
    return step


def generate(cfg: ModelConfig, params, tokens, key, *, max_new_tokens: int,
             temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
             eos_id: Optional[int] = None, encoder_embeds=None):
    """tokens: (B, Lp) fixed-length prompts.

    Returns a dict with:

    - ``sequences`` (B, Lp + max_new): prompt followed by generated
      tokens; once a sequence emits ``eos_id`` every later position holds
      ``eos_id`` (the sampler is bypassed for finished rows).
    - ``response_mask`` (B, Lp + max_new) bool: True exactly on generated
      tokens *up to and including* the first EOS; False on all prompt
      positions and on the forced-EOS padding after a sequence finishes.
      (PPO losses therefore credit the EOS emission but never the
      padding.)
    - ``cache``: the filled KV cache (position ``Lp + max_new``).

    With ``eos_id=None`` no sequence ever finishes and the mask is True
    on the whole response region.
    """
    B, Lp = tokens.shape
    total = Lp + max_new_tokens
    cache = T.init_cache(cfg, B, total)
    logits, cache = prefill(cfg, params, tokens, cache,
                            encoder_embeds=encoder_embeds)

    step = decode_scan_step(cfg, params, temperature=temperature,
                            top_k=top_k, top_p=top_p, eos_id=eos_id,
                            encoder_embeds=encoder_embeds)
    pos0 = jnp.full((B,), Lp, jnp.int32)
    done0 = jnp.zeros((B,), bool)
    (_, cache, _, _, _), (toks, was_done) = jax.lax.scan(
        step, (logits, cache, key, pos0, done0), None,
        length=max_new_tokens)
    gen = toks.T                                   # (B, max_new)
    resp_mask = (~was_done.T)
    sequences = jnp.concatenate([tokens, gen], axis=1)
    mask = jnp.concatenate(
        [jnp.zeros((B, Lp), bool), resp_mask], axis=1)
    return {"sequences": sequences, "response_mask": mask, "cache": cache}
