"""Pallas TPU flash-decode: single-token GQA attention over a KV cache.

This is the paper's RLHF generation hot loop — one query token per
sequence attends to S cached keys; arithmetic intensity is O(1) so the
kernel is purely HBM-bandwidth-bound and the goal is to stream K/V tiles
through VMEM exactly once at full bandwidth.

Tiling: grid = (B, KV, ns); the KV length is the sequential axis with
online-softmax scratch carried across tiles (the TPU analogue of GPU
split-KV decode kernels).  The G query heads of a KV group ride along in
one (G, D) tile so each K/V byte loaded serves all G heads (GQA's whole
point — it multiplies effective bandwidth by G).

Layout — this kernel consumes the **dense** cache layout: each sequence
owns a contiguous per-slot slab of each layer, q: (B, KV, G, D); k/v
cache: the stacked arena of a scan segment, (n, B, KV, S, D), and the
layer index ``layer``; lens: (B,) int32 number of valid rows.  The
decode step carries the arena through its layer scan and hands the
kernel the whole stack: ``layer`` arrives by scalar prefetch and the K/V
index maps select ``(layer, b, h, tile)``, so a layer's slab is read
where it lies and never sliced out.  The valid rows of
a slot are always a prefix of its slab (a ring buffer fills slots
``0..S-1`` in order and RoPE is pre-applied, so slot order is free), so
one length per row replaces a ``(B, S)`` mask.  ``lens`` arrives by
scalar prefetch: tiles wholly past it skip their dot-products and their
K/V index map stays on the last needed tile, so no DMA is issued for
them.  It serves ``generate()`` / fixed-batch PPO decode, the dense
continuous scheduler (``kv_layout="dense"``), and sliding-window /
ring-buffer caches, which are inherently contiguous.  The **paged**
serving layout — a shared block pool indexed through per-slot block
tables, selected by ``kv_layout="paged"`` in
:class:`repro.serving.engine.GenerationEngine` — is served by the
sibling kernel in :mod:`repro.kernels.paged_attention`, which reuses
this online-softmax scheme but walks the block table (via scalar
prefetch) as its sequential grid axis instead of a contiguous S axis.

TPU tiling: the last two dims of every block are either the array's own
or multiples of (8, 128).  ``s_block`` must therefore divide S and be a
multiple of 128 or S itself (see ``ops.decode_block``); the int8 scale
planes ``(n, B, KV, S)`` travel as ``(1, KV, s_block)`` blocks of the
layer holding every KV head, and the kernel reads its head's row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def decode_body(n_valid, q_ref, k_ref, v_ref, rest, *, blk, n_steps,
                quant):
    """One grid step (b, h, i) of a decode kernel, shared with the paged
    kernel: fold the i-th ``blk``-row K/V tile of head h into the
    online-softmax scratch, masking rows at or past ``n_valid``, and
    write the output on the last step.  ``rest`` is (o, m, l, acc) refs,
    preceded by the (k_scale, v_scale) refs of an int8 cache, whose
    ``(1, KV, blk)`` blocks hold every head's row."""
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    h = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tiles wholly past the length contribute nothing: skip their dots
    @pl.when(i * blk < n_valid)
    def _accumulate():
        q = q_ref[0, 0].astype(jnp.float32)          # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (blk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        D = q.shape[-1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (1.0 / np.sqrt(D))                   # (G, blk)
        if quant:                                    # dequant K on scores
            s = s * ks_ref[0, pl.ds(h, 1), :]        # (1, blk) fp32
        rows = i * blk + jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1)
        s = jnp.where(rows < n_valid, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(-1)
        if quant:                                    # dequant V on probs
            p = p * vs_ref[0, pl.ds(h, 1), :]
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == n_steps - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def _kernel(lens_ref, layer_ref, q_ref, k_ref, v_ref, *rest, sb, ns, quant):
    decode_body(lens_ref[pl.program_id(0)], q_ref, k_ref, v_ref, rest,
                blk=sb, n_steps=ns, quant=quant)


def _decode_call(q, k_cache, v_cache, scales, lens, layer, s_block,
                 interpret):
    """Grid (B, KV, ns) over layer ``layer``'s slabs of the stacked arena;
    ``lens`` and ``layer`` are the scalar-prefetch operands, so the index
    maps pick the layer's tiles where they lie and no per-layer slab is
    ever sliced out."""
    B, KV, G, D = q.shape
    S = k_cache.shape[3]
    sb = min(s_block, S)
    assert S % sb == 0, (S, sb)
    ns = S // sb

    def last_tile(b, ik, lens):
        # tiles past the row's length repeat the last needed tile's index,
        # so the pipeline issues no DMA for them
        return jnp.minimum(ik, jnp.maximum(lens[b] - 1, 0) // sb)

    def q_map(b, h, ik, lens, layer):
        return (b, h, 0, 0)

    def kv_map(b, h, ik, lens, layer):
        return (layer[0], b, h, last_tile(b, ik, lens), 0)

    def scale_map(b, h, ik, lens, layer):
        return (layer[0], b, 0, last_tile(b, ik, lens))

    kv_spec = pl.BlockSpec((None, 1, 1, sb, D), kv_map)
    scale_spec = pl.BlockSpec((None, 1, KV, sb), scale_map)
    kernel = functools.partial(_kernel, sb=sb, ns=ns, quant=bool(scales))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, ns),
        in_specs=[pl.BlockSpec((1, 1, G, D), q_map), kv_spec, kv_spec,
                  *[scale_spec] * len(scales)],
        out_specs=pl.BlockSpec((1, 1, G, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(lens.astype(jnp.int32), jnp.reshape(layer, (1,)).astype(jnp.int32),
      q, k_cache, v_cache, *[x.astype(jnp.float32) for x in scales])


def decode_attention_fwd(q, k_cache, v_cache, lens, layer, *, s_block=512,
                         interpret=False):
    """q: (B, KV, G, D); k/v: the stacked arena (n, B, KV, S, D), read at
    layer ``layer`` (an int32 scalar); lens: (B,) int32 valid-row counts
    (rows ``0..lens-1`` of each slab are attended)."""
    return _decode_call(q, k_cache, v_cache, (), lens, layer, s_block,
                        interpret)


# ===================================================================== #
# Int8-KV variant: fused dequant inside the online-softmax accumulation.
#
# K/V tiles stay int8 all the way from HBM into the dot-products; the
# per-(token, kv-head) absmax scales enter as rank-1 factors on the
# *score* and *probability* tiles instead:
#
#   s[g, t]  = (q[g] . k_int8[t]) * k_scale[t] / sqrt(D)
#   acc[g]  += sum_t (p[g, t] * v_scale[t]) * v_int8[t]
#
# which is algebraically identical to dequantizing K/V first but never
# materializes an fp copy of the cache — the HBM read per token is
# 2*D int8 + 2 fp32 scales instead of 2*D fp values, which is the whole
# memory-bandwidth win of int8 KV on this bandwidth-bound kernel.
# ===================================================================== #
def decode_attention_quant_fwd(q, k_cache, v_cache, k_scale, v_scale, lens,
                               layer, *, s_block=512, interpret=False):
    """q: (B, KV, G, D) fp; k/v: the stacked int8 arena (n, B, KV, S, D);
    k/v_scale: (n, B, KV, S) fp32; all read at layer ``layer``; lens:
    (B,) int32 valid-row counts."""
    return _decode_call(q, k_cache, v_cache, (k_scale, v_scale), lens, layer,
                        s_block, interpret)
