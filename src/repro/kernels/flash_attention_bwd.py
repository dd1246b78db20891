"""Pallas TPU flash attention BACKWARD (FlashAttention-2 style).

Two kernels, mirroring the standard TPU split:

- ``_dkv_kernel``  grid (B, KV, nk, nq): for a fixed K/V tile, stream the
  q/do tiles on the sequential axis, accumulating dK/dV in VMEM scratch;
  all G query heads of the KV group are processed in-tile (their
  contributions sum into the same dK/dV — GQA's bwd reduction).
- ``_dq_kernel``   grid (B, KV, nq, nk): for a fixed q tile, stream K/V
  tiles, accumulating dQ.

Both recompute p = exp(s - lse) from the forward's saved logsumexp —
no (Lq, Lk) tensor ever exists.  ``delta = rowsum(dO * O)`` is
precomputed by the ops wrapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _tile_p_ds(q, g, k, v, lse, delta, qpos, kpos, lk_valid, causal,
               window, scale):
    """Recompute p and ds for one (qb, kb) tile of one query head.
    q, g: (qb, D); k, v: (kb, D); lse, delta: (qb,); qpos: (qb, kb) and
    kpos: (qb, kb) absolute positions (2-D iotas: Mosaic builds no
    repeated or concatenated position vectors)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    m = kpos < lk_valid
    if causal:
        m = m & (qpos >= kpos)
    if window is not None:
        m = m & ((qpos - kpos) < window)
    lse_safe = jnp.where(lse <= NEG_INF / 2, 0.0, lse)
    p = jnp.where(m, jnp.exp(s - lse_safe[:, None]), 0.0)
    dp = jax.lax.dot_general(g, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    return p, ds


def _positions(iq, ik, q_block, k_block, q_offset):
    shape = (q_block, k_block)
    qpos = q_offset + iq * q_block + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0)
    kpos = ik * k_block + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return qpos, kpos


def _dkv_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                causal, window, q_block, k_block, nq, lk_valid, q_offset,
                sm_scale):
    iq = pl.program_id(3)
    ik = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    G = q_ref.shape[2]
    D = q_ref.shape[-1]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    qpos, kpos = _positions(iq, ik, q_block, k_block, q_offset)
    scale = 1.0 / np.sqrt(D) if sm_scale is None else sm_scale
    # the G query heads of this KV group all sum into the same dK/dV
    for gi in range(G):
        q = q_ref[0, 0, gi].astype(jnp.float32)      # (qb, D)
        g = g_ref[0, 0, gi].astype(jnp.float32)
        p, ds = _tile_p_ds(q, g, k, v, lse_ref[0, 0, gi],
                           delta_ref[0, 0, gi], qpos, kpos, lk_valid,
                           causal, window, scale)
        dv_acc[...] += jax.lax.dot_general(
            p, g, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _done():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *,
               causal, window, q_block, k_block, nk, lk_valid, q_offset,
               sm_scale):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    G = q_ref.shape[2]
    D = q_ref.shape[-1]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    qpos, kpos = _positions(iq, ik, q_block, k_block, q_offset)
    scale = 1.0 / np.sqrt(D) if sm_scale is None else sm_scale
    for gi in range(G):
        q = q_ref[0, 0, gi].astype(jnp.float32)      # (qb, D)
        g = g_ref[0, 0, gi].astype(jnp.float32)
        _, ds = _tile_p_ds(q, g, k, v, lse_ref[0, 0, gi],
                           delta_ref[0, 0, gi], qpos, kpos, lk_valid,
                           causal, window, scale)
        dq_acc[gi] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def flash_attention_bwd(q, k, v, g, lse, delta, *, causal=True, window=None,
                        q_block=256, k_block=256, q_offset=None,
                        lk_valid=None, sm_scale=None, interpret=False):
    """q, g: (B, KV, G, Lq, D); k, v: (B, KV, Lk, D);
    lse, delta: (B, KV, G, Lq).  Returns (dq, dk, dv).  ``q_offset`` and
    ``lk_valid`` and ``sm_scale`` as in :func:`flash_attention_fwd`."""
    B, KV, G, Lq, D = q.shape
    Lk = k.shape[2]
    q_block = min(q_block, Lq)
    k_block = min(k_block, Lk)
    assert Lq % q_block == 0 and Lk % k_block == 0
    nq, nk = Lq // q_block, Lk // k_block
    lk_valid = Lk if lk_valid is None else lk_valid
    q_offset = lk_valid - Lq if q_offset is None else q_offset

    common = dict(causal=causal, window=window, q_block=q_block,
                  k_block=k_block, lk_valid=lk_valid, q_offset=q_offset,
                  sm_scale=sm_scale)
    q_spec = pl.BlockSpec((1, 1, G, q_block, D),
                          lambda b, h, ik, iq: (b, h, 0, iq, 0))
    kv_spec_dkv = pl.BlockSpec((1, 1, k_block, D),
                               lambda b, h, ik, iq: (b, h, ik, 0))
    sc_spec = pl.BlockSpec((1, 1, G, q_block),
                           lambda b, h, ik, iq: (b, h, 0, iq))

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, nq=nq, **common),
        grid=(B, KV, nk, nq),
        in_specs=[q_spec, q_spec, kv_spec_dkv, kv_spec_dkv, sc_spec,
                  sc_spec],
        out_specs=[kv_spec_dkv, kv_spec_dkv],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((k_block, D), jnp.float32),
                        pltpu.VMEM((k_block, D), jnp.float32)],
        interpret=interpret,
    )(q, g, k, v, lse, delta)

    q_spec2 = pl.BlockSpec((1, 1, G, q_block, D),
                           lambda b, h, iq, ik: (b, h, 0, iq, 0))
    kv_spec2 = pl.BlockSpec((1, 1, k_block, D),
                            lambda b, h, iq, ik: (b, h, ik, 0))
    sc_spec2 = pl.BlockSpec((1, 1, G, q_block),
                            lambda b, h, iq, ik: (b, h, 0, iq))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, nk=nk, **common),
        grid=(B, KV, nq, nk),
        in_specs=[q_spec2, q_spec2, kv_spec2, kv_spec2, sc_spec2, sc_spec2],
        out_specs=q_spec2,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((G, q_block, D), jnp.float32)],
        interpret=interpret,
    )(q, g, k, v, lse, delta)
    return dq, dk, dv
