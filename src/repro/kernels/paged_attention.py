"""Pallas TPU paged flash-decode: single-token GQA attention over a
block-pooled KV cache.

The dense flash-decode kernel (:mod:`repro.kernels.decode_attention`)
streams a per-sequence ``(S, D)`` cache slab; its HBM footprint is
``slots * max_seq_len`` rows whether or not a sequence uses them.  Here
the cache lives in a shared pool of fixed ``block_size``-token blocks
and each sequence owns only the blocks its tokens occupy; the kernel
walks the sequence's *block table* as the sequential grid axis.

Tiling: grid = (B, KV, nb) with the block axis sequential and the same
online-softmax scratch carry as the dense kernel.  The G query heads of
a KV group ride along in one (G, D) tile so every K/V byte loaded still
serves all G heads — paging must not give up GQA's bandwidth
amplification, which is the whole point of the decode kernel.

Block indirection uses **scalar prefetch**: the block table and
per-sequence lengths arrive as scalar-prefetch operands, so the
``index_map`` of the K/V pool can compute the DMA source block
(``table[b, ib]``) before the kernel body runs — the TPU analogue of
vLLM's PagedAttention gather.

Layout: q: (B, KV, G, D); k/v pool: the stacked pool of a scan segment,
(n, nblocks, KV, bs, D), read at layer ``layer`` (a third scalar-prefetch
operand, so a layer's pool is never sliced out) — the KV head axis sits
ahead of the block's rows so that one grid step's tile is a
``(bs, D)`` slab whose last two dims are the array's own (the TPU tiling
rule; a ``(bs, 1, D)`` slice of a ``(bs, KV, D)`` block is refused);
block_tables: (B, nb) int32 (entries past a sequence's allocated prefix
point at the trash block 0); lens: (B,) int32 = number of valid rows
(``pos + 1``).  RoPE is pre-applied to cached keys, so block order is
free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import decode_body


def _kernel(tbl_ref, lens_ref, layer_ref, q_ref, k_ref, v_ref, *rest, bs, nb,
            quant):
    decode_body(lens_ref[pl.program_id(0)], q_ref, k_ref, v_ref, rest,
                blk=bs, n_steps=nb, quant=quant)


# ===================================================================== #
# Int8-KV paged variant: the pool stores int8 K/V blocks plus per-row
# fp32 scale planes (nblocks, KV, bs); dequant is fused into the
# online-softmax accumulation exactly as in the dense int8 kernel
# (k_scale multiplies the score tile, v_scale the probability tile), so
# the DMA per cached token stays at 2*D int8 + 2 fp32 scales — fp K/V is
# never materialized.  The block-table walk (scalar-prefetch index_map)
# is identical to the fp kernel; a scale tile holds every KV head's row
# of the block and rides the same indirection.
# ===================================================================== #
def _paged_call(q, k_pool, v_pool, scales, block_tables, lens, layer,
                interpret):
    B, KV, G, D = q.shape
    bs = k_pool.shape[3]
    nb = block_tables.shape[1]

    def q_map(b, h, ib, tbl, lens, layer):
        return (b, h, 0, 0)

    def kv_map(b, h, ib, tbl, lens, layer):
        return (layer[0], tbl[b, ib], h, 0, 0)

    def scale_map(b, h, ib, tbl, lens, layer):
        return (layer[0], tbl[b, ib], 0, 0)

    in_specs = [pl.BlockSpec((1, 1, G, D), q_map),
                pl.BlockSpec((None, 1, 1, bs, D), kv_map),
                pl.BlockSpec((None, 1, 1, bs, D), kv_map)]
    in_specs += [pl.BlockSpec((None, 1, KV, bs), scale_map)] * len(scales)
    kernel = functools.partial(_kernel, bs=bs, nb=nb, quant=bool(scales))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, KV, nb),
        in_specs=in_specs,
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
    )(block_tables.astype(jnp.int32), lens.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, k_pool, v_pool,
      *[x.astype(jnp.float32) for x in scales])


def paged_decode_attention_quant_fwd(q, k_pool, v_pool, k_scale, v_scale,
                                     block_tables, lens, layer, *,
                                     interpret=False):
    """q: (B, KV, G, D) fp; k/v pool: (n, nblocks, KV, bs, D) int8;
    k/v_scale: (n, nblocks, KV, bs) fp32; all read at layer ``layer``;
    block_tables: (B, nb) int32; lens: (B,) int32."""
    return _paged_call(q, k_pool, v_pool, (k_scale, v_scale), block_tables,
                       lens, layer, interpret)


def paged_decode_attention_fwd(q, k_pool, v_pool, block_tables, lens, layer,
                               *, interpret=False):
    """q: (B, KV, G, D); k/v pool: (n, nblocks, KV, bs, D), read at layer
    ``layer``; block_tables: (B, nb) int32; lens: (B,) int32."""
    return _paged_call(q, k_pool, v_pool, (), block_tables, lens, layer,
                       interpret)
