"""Pallas TPU flash attention (forward) — prefill / training attention.

Tiling: grid = (B, KV, G, nq, nk); the last grid axis is sequential on TPU,
so the online-softmax running stats (m, l, acc) live in VMEM scratch and
the output tile is written on the final kv step.  Block sizes default to
MXU-aligned (q_block x head_dim) = (256, 128) tiles; K/V stream through
VMEM in (k_block, head_dim) tiles so the working set is
O(q_block·D + k_block·D + q_block·k_block) regardless of context length.

Layout contract (see ops.py for the (B, L, H, D) adapter):
    q: (B, KV, G, Lq, D)   k, v: (B, KV, Lk, D)   out: like q
Query positions are aligned to the END of the key axis (decode-style
continuation): qpos = arange(Lq) + (Lk - Lq).  A caller that pads Lq and
Lk up to tile multiples passes the true ``q_offset`` and ``lk_valid``;
keys at or past ``lk_valid`` are masked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            causal, window, k_block, nk, q_offset, lk_valid, sm_scale):
    ik = pl.program_id(4)
    iq = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, 0].astype(jnp.float32)            # (qb, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (kb, D)
    v = v_ref[0, 0].astype(jnp.float32)
    qb, D = q.shape

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * (1.0 / np.sqrt(D) if sm_scale is None else sm_scale)

    qpos = q_offset + iq * qb + jax.lax.broadcasted_iota(
        jnp.int32, (qb, k_block), 0)
    kpos = ik * k_block + jax.lax.broadcasted_iota(
        jnp.int32, (qb, k_block), 1)
    mask = kpos < lk_valid
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal=True, window=None,
                        q_block=256, k_block=512, q_offset=None,
                        lk_valid=None, sm_scale=None, interpret=False):
    """q: (B, KV, G, Lq, D); k, v: (B, KV, Lk, D).  ``lk_valid`` (default
    Lk) counts the real keys; ``q_offset`` (default ``lk_valid - Lq``) is
    the absolute position of query row 0; ``sm_scale`` (default
    1/sqrt(D)) multiplies the scores."""
    B, KV, G, Lq, D = q.shape
    Lk = k.shape[2]
    lk_valid = Lk if lk_valid is None else lk_valid
    q_offset = lk_valid - Lq if q_offset is None else q_offset
    q_block = min(q_block, Lq)
    k_block = min(k_block, Lk)
    assert Lq % q_block == 0 and Lk % k_block == 0, (Lq, q_block, Lk,
                                                     k_block)
    nq, nk = Lq // q_block, Lk // k_block
    grid = (B, KV, G, nq, nk)

    kernel = functools.partial(_kernel, causal=causal, window=window,
                               k_block=k_block, nk=nk, q_offset=q_offset,
                               lk_valid=lk_valid, sm_scale=sm_scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, q_block, D),
                         lambda b, h, g, iq, ik: (b, h, g, iq, 0)),
            pl.BlockSpec((1, 1, k_block, D),
                         lambda b, h, g, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, k_block, D),
                         lambda b, h, g, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, q_block, D),
                               lambda b, h, g, iq, ik: (b, h, g, iq, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
