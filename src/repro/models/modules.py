"""Model primitives: norms, RoPE (with YaRN), attention (GQA / qk-norm /
sliding-window / MLA / cross), SwiGLU MLP, dropless MoE over the experts a
model holds (grouped matmuls), and the Mamba2 SSD mixer (chunked scan for
train/prefill, O(1) recurrence for decode).

Everything is functional: ``params`` are nested dicts of arrays; the
structure (shapes + logical sharding axes) comes from ``ParamSpec`` trees so
the sharding layer has a single source of truth.

Attention is implemented flash-style (block-wise online softmax via
``lax.scan`` over KV blocks) in pure jnp — the full L×L score matrix is
never materialized, which is what lets the 32k-prefill and 4k×256-batch
training graphs compile within per-chip memory on the production mesh.  On
a TPU (``cfg.pallas_kernels``) the same math dispatches to the Pallas
kernels in ``repro.kernels``; the jnp code here is then their reference.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig

NEG_INF = -1e30


def constrain_batch(x, batch_axes):
    """Pin the leading (batch) axis of an activation to the data mesh axes.
    Without this, GSPMD propagation can replicate the batch (it prefers the
    embed-table sharding through the gather) and per-device activation
    memory blows up by the data-parallel factor."""
    if not batch_axes or x is None:
        return x
    from jax.sharding import PartitionSpec as P
    lead = batch_axes[0] if len(batch_axes) == 1 else tuple(batch_axes)
    return jax.lax.with_sharding_constraint(
        x, P(lead, *([None] * (x.ndim - 1))))


_TP_LOGICAL = {"heads", "kv_heads", "mlp", "experts", "vocab"}


def wgather(w, cfg, axes):
    """§Perf weight-gather-at-use: constrain a weight to its ZeRO layout
    with the data axes stripped (model/TP shards kept).  XLA then
    all-gathers the WEIGHT once per use instead of partial-summing the
    matmul and all-reducing the (much larger) activation — the dominant
    training collective otherwise.  ``axes`` are the weight's logical
    axes (layer-sliced, no leading "layers")."""
    if not (cfg.weight_gather and cfg.batch_axes and cfg.tp_axis):
        return w
    from jax.sharding import PartitionSpec as P
    entries = []
    used = False
    for dim, a in zip(w.shape, axes):
        if a in _TP_LOGICAL and not used and dim % cfg.tp_size == 0:
            entries.append(cfg.tp_axis)
            used = True
        else:
            entries.append(None)
    # barrier pins the f32->bf16 convert BEFORE the gather so the
    # collective moves half the bytes (XLA otherwise reorders to
    # gather-f32-then-convert)
    w = jax.lax.optimization_barrier(w.astype(cfg.cdtype))
    return jax.lax.with_sharding_constraint(w, P(*entries))


def constrain_axis(x, cfg, axis: int, dim_divisor: int = 16):
    """Additionally shard activation axis ``axis`` over the TP mesh axis
    (used for SSD heads — the (b,c,h,q,q) intra-chunk decay tensors are the
    memory peak of Mamba2 training and shard cleanly over heads)."""
    if not cfg.tp_axis or x is None or x.shape[axis] % dim_divisor:
        return x
    from jax.sharding import PartitionSpec as P
    spec = [None] * x.ndim
    if cfg.batch_axes:
        spec[0] = (cfg.batch_axes[0] if len(cfg.batch_axes) == 1
                   else tuple(cfg.batch_axes))
    spec[axis] = cfg.tp_axis
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ===================================================================== #
# Param specs
# ===================================================================== #
@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple              # logical axis names, None = never sharded
    init: str = "normal"     # normal | zeros | ones
    scale: float = 1.0       # multiplier on 1/sqrt(fan_in)


def materialize(spec: ParamSpec, key, dtype) -> jnp.ndarray:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    start = 1 if (spec.axes and spec.axes[0] == "layers") else 0
    shp = spec.shape[start:]
    fan_in = shp[0] if len(shp) == 1 else int(np.prod(shp[:-1]))
    std = spec.scale / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, spec.shape) * std).astype(dtype)


def init_tree(specs, key, dtype):
    """Materialize a pytree of ParamSpec into arrays (split keys by path)."""
    leaves, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    vals = [materialize(s, k, dtype) for s, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(treedef, vals)


# ===================================================================== #
# Norms
# ===================================================================== #
def rmsnorm(x, weight, eps: float = 1e-5, use_pallas: bool = False):
    if use_pallas and x.ndim >= 2:
        from repro.kernels import ops as kops
        return kops.rmsnorm(x, weight, eps=eps)
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)).astype(dt)


def rmsnorm_gated(x, z, weight, eps: float = 1e-5):
    """Mamba2-style gated RMSNorm: norm(x * silu(z))."""
    return rmsnorm(x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                   weight, eps)


# ===================================================================== #
# RoPE
# ===================================================================== #
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x, positions, theta: float, inv_freq=None,
               mscale: float = 1.0):
    """x: (..., L, H, D) or (..., L, D); positions: (..., L).  Rotates
    feature pairs (2i, 2i+1) by position * ``inv_freq[i]`` (default
    theta**(-2i/D)); ``mscale`` multiplies cos and sin."""
    d = x.shape[-1]
    inv = jnp.asarray(rope_freqs(d, theta) if inv_freq is None else inv_freq,
                      jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * inv        # (..., L, d/2)
    if x.ndim == ang.ndim + 1:                                   # heads axis
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = x[..., ::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def rope(cfg: ModelConfig, x, positions):
    """RoPE as ``cfg`` states it: YaRN's frequencies and cos/sin scale
    when ``cfg.yarn_factor`` is set."""
    if not cfg.yarn_factor:
        return apply_rope(x, positions, cfg.rope_theta)
    return apply_rope(x, positions, cfg.rope_theta,
                      cfg.rope_inv_freq(x.shape[-1]), cfg.rope_mscale)


# ===================================================================== #
# Flash attention (pure jnp, block-wise online softmax, custom VJP)
#
# The backward pass recomputes attention probabilities block-by-block
# (FlashAttention-2 style) instead of letting scan-AD stash every (q,k)
# tile -- without this, a 4k x 4k training graph materializes hundreds of
# GiB of per-block residuals.  This function doubles as the numerical
# oracle for the Pallas TPU kernel in repro/kernels.
# ===================================================================== #
def _tile_mask(qpos, kpos, Lk, causal, window):
    mask = (kpos < Lk)[None, :]
    if causal:
        mask = mask & (qpos[:, None] >= kpos[None, :])
    if window is not None:
        mask = mask & ((qpos[:, None] - kpos[None, :]) < window)
    return mask                                    # (q_block, k_block)


def flash_attention(q, k, v, *, causal=True, window=None, q_block=512,
                    k_block=1024, qpos0=0, scale=None):
    """Memory-efficient attention.

    q: (B, Lq, H, D); k, v: (B, Lk, KV, D) with H = KV * G.
    Never materializes (Lq, Lk); scans KV blocks with online softmax.
    ``qpos0`` offsets query positions (prefill continuation); ``window``
    applies sliding-window masking; ``scale`` (default 1/sqrt(D))
    multiplies the scores.
    """
    Lq, Lk = q.shape[1], k.shape[1]
    meta = (bool(causal), window, int(min(q_block, Lq)),
            int(min(k_block, Lk)), int(qpos0), scale)
    return _flash(meta, q, k, v)


def _blockify(x, blk):
    """(B, L, ...) -> ((B, n, blk, ...), n) with zero padding."""
    B, L = x.shape[0], x.shape[1]
    rest = x.shape[2:]
    n = -(-L // blk)
    xp = jnp.pad(x, ((0, 0), (0, n * blk - L)) + ((0, 0),) * len(rest))
    return xp.reshape((B, n, blk) + rest), n


def _flash_fwd_impl(meta, q, k, v):
    causal, window, q_block, k_block, qpos0, scale = meta
    B, Lq, H, D = q.shape
    Lk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(D) if scale is None else scale
    q5 = q.reshape(B, Lq, KV, G, D)
    qp, nq = _blockify(q5, q_block)               # (B,nq,qb,KV,G,D)
    kp, nk = _blockify(k, k_block)                # (B,nk,kb,KV,D)
    vp, _ = _blockify(v, k_block)

    ks = jnp.moveaxis(kp, 1, 0)                   # (nk,B,kb,KV,D)
    vs = jnp.moveaxis(vp, 1, 0)

    def q_block_fn(xs):
        qb, iq = xs
        qpos = qpos0 + iq * q_block + jnp.arange(q_block)

        def kv_step(carry, xs2):
            m, l, acc = carry
            kb, vb, ik = xs2
            kpos = ik * k_block + jnp.arange(k_block)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _tile_mask(qpos, kpos, Lk, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, q_block), jnp.float32)
        a0 = jnp.zeros((B, KV, G, q_block, D), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      (ks, vs, jnp.arange(nk)))
        out = acc / jnp.maximum(l[..., None], 1e-20)
        lse = m + jnp.log(jnp.maximum(l, 1e-20))   # (B,KV,G,qb)
        return out, lse

    qb_stack = jnp.moveaxis(qp, 1, 0)              # (nq,B,qb,KV,G,D)
    outs, lses = jax.lax.map(q_block_fn, (qb_stack, jnp.arange(nq)))
    out = jnp.moveaxis(outs, 0, 3)                 # (B,KV,G,nq,qb,D)
    out = out.reshape(B, KV, G, nq * q_block, D)[:, :, :, :Lq]
    out = jnp.moveaxis(out, 3, 1).reshape(B, Lq, H, D).astype(q.dtype)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, KV, G, nq * q_block)[..., :Lq]
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(meta, q, k, v):
    return _flash_fwd_impl(meta, q, k, v)[0]


def _flash_fwd(meta, q, k, v):
    out, lse = _flash_fwd_impl(meta, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd(meta, res, g):
    causal, window, q_block, k_block, qpos0, scale = meta
    q, k, v, out, lse = res
    B, Lq, H, D = q.shape
    Lk, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = 1.0 / np.sqrt(D) if scale is None else scale

    q5 = q.reshape(B, Lq, KV, G, D)
    g5 = g.reshape(B, Lq, KV, G, D)
    o5 = out.reshape(B, Lq, KV, G, D)
    delta = jnp.sum(g5.astype(jnp.float32) * o5.astype(jnp.float32),
                    axis=-1)                               # (B,Lq,KV,G)
    delta = jnp.moveaxis(jnp.moveaxis(delta, 1, 3), 1, 1)  # (B,KV,G,Lq)

    qp, nq = _blockify(q5, q_block)
    gp, _ = _blockify(g5, q_block)
    kp, nk = _blockify(k, k_block)
    vp, _ = _blockify(v, k_block)
    Skp = nk * k_block
    kp_flat = kp.reshape(B, Skp, KV, D)
    vp_flat = vp.reshape(B, Skp, KV, D)
    pad_q = nq * q_block - Lq
    lse_p = jnp.pad(lse, ((0, 0),) * 3 + ((0, pad_q),),
                    constant_values=NEG_INF)
    lse_b = lse_p.reshape(B, KV, G, nq, q_block)
    delta_p = jnp.pad(delta, ((0, 0),) * 3 + ((0, pad_q),))
    delta_b = delta_p.reshape(B, KV, G, nq, q_block)

    def q_step(carry, xs):
        dk, dv = carry                                     # (B,Skp,KV,D) f32
        qb, gb, lse_q, delta_q, iq = xs
        qpos = qpos0 + iq * q_block + jnp.arange(q_block)
        lse_safe = jnp.where(lse_q <= NEG_INF / 2, 0.0, lse_q)

        def kv_step(inner, ik):
            dk, dv, dq = inner
            k0 = ik * k_block
            kb = jax.lax.dynamic_slice_in_dim(kp_flat, k0, k_block, 1)
            vb = jax.lax.dynamic_slice_in_dim(vp_flat, k0, k_block, 1)
            kpos = k0 + jnp.arange(k_block)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _tile_mask(qpos, kpos, Lk, causal, window)
            p = jnp.where(mask[None, None, None],
                          jnp.exp(s - lse_safe[..., None]), 0.0)
            gb32 = gb.astype(jnp.float32)
            dv_blk = jnp.einsum("bkgqs,bqkgd->bskd", p, gb32)
            dp = jnp.einsum("bqkgd,bskd->bkgqs", gb32,
                            vb.astype(jnp.float32))
            ds = p * (dp - delta_q[..., None]) * scale
            dq = dq + jnp.einsum("bkgqs,bskd->bqkgd", ds,
                                 kb.astype(jnp.float32))
            dk_blk = jnp.einsum("bkgqs,bqkgd->bskd", ds,
                                qb.astype(jnp.float32))
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, jax.lax.dynamic_slice_in_dim(dk, k0, k_block, 1)
                + dk_blk, k0, 1)
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, jax.lax.dynamic_slice_in_dim(dv, k0, k_block, 1)
                + dv_blk, k0, 1)
            return (dk, dv, dq), None

        dq0 = jnp.zeros((B, q_block, KV, G, D), jnp.float32)
        (dk, dv, dq), _ = jax.lax.scan(kv_step, (dk, dv, dq0),
                                       jnp.arange(nk))
        return (dk, dv), dq

    dk0 = jnp.zeros((B, Skp, KV, D), jnp.float32)
    dv0 = jnp.zeros((B, Skp, KV, D), jnp.float32)
    (dk, dv), dqs = jax.lax.scan(
        q_step, (dk0, dv0),
        (jnp.moveaxis(qp, 1, 0), jnp.moveaxis(gp, 1, 0),
         jnp.moveaxis(lse_b, 3, 0), jnp.moveaxis(delta_b, 3, 0),
         jnp.arange(nq)))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, nq * q_block, KV, G, D)[:, :Lq]
    dq = dq.reshape(B, Lq, H, D).astype(q.dtype)
    dk = dk[:, :Lk].astype(k.dtype)
    dv = dv[:, :Lk].astype(v.dtype)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def _attend(q, k, v, lens):
    """Single-token attention over one layer's (B, KV, S, D) slabs, rows
    ``0..lens-1`` valid: the jnp decode math of every layout."""
    B, H, D = q.shape
    KV, S = k.shape[1], k.shape[2]
    valid_mask = jnp.arange(S)[None, :] < lens[:, None]
    qs = q.reshape(B, KV, H // KV, D)
    s = jnp.einsum("bkgd,bksd->bkgs", qs, k,
                   preferred_element_type=jnp.float32) / np.sqrt(D)
    s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, H, D).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, lens, layer, use_pallas=False):
    """Single-token attention over a (possibly ring-buffer) KV arena.

    q: (B, H, D); k_cache/v_cache: the stacked arena of a scan segment,
    (n, B, KV, S, D), read at layer ``layer``; lens: (B,) number of
    valid rows, which are always the slot prefix ``0..lens-1`` (a ring
    buffer fills its slots in order).  Returns (B, H, D).  RoPE is
    pre-applied to cached keys, so slot order inside the ring buffer is
    irrelevant (softmax is order-invariant).
    """
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.decode_attention(q, k_cache, v_cache, lens, layer)
    return _attend(q, k_cache[layer], v_cache[layer], lens)


# ===================================================================== #
# GQA attention layer (qk-norm, sliding window, ring-buffer cache)
# ===================================================================== #
def attn_specs(cfg: ModelConfig) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = {
        "wq": ParamSpec((D, H * hd), ("embed", "heads")),
        "wk": ParamSpec((D, KV * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((D, KV * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((H * hd, D), ("heads", "embed")),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((hd,), (None,), "ones")
        s["k_norm"] = ParamSpec((hd,), (None,), "ones")
    return s


def attn_cache_shape(cfg: ModelConfig, batch: int, max_len: int,
                     window: Optional[int]):
    """Dense arena of one layer, KV-head-major ``(batch, KV, S, D)`` like
    the paged pool's blocks: the decode kernel reads a head's ``(S, D)``
    rows where they lie.  With ``cfg.kv_quant`` the arena is int8 plus
    fp32 ``k_scale`` / ``v_scale`` planes ``(batch, KV, S)``."""
    S = max_len if window is None else min(window, max_len)
    out = dict(k=(batch, cfg.n_kv_heads, S, cfg.head_dim),
               v=(batch, cfg.n_kv_heads, S, cfg.head_dim))
    if cfg.kv_quant:
        out["k_scale"] = (batch, cfg.n_kv_heads, S)
        out["v_scale"] = (batch, cfg.n_kv_heads, S)
    return out


def paged_attn_cache_shape(cfg: ModelConfig, num_blocks: int,
                           block_size: int):
    """Paged layout: a shared pool of ``num_blocks`` fixed-size KV blocks
    (block 0 reserved as the trash block) instead of a per-slot
    ``(batch, S)`` arena.  A block is KV-head-major, ``(KV, bs, D)``:
    the decode kernel's tile is one head's ``(bs, D)`` rows, and TPU
    tiling refuses a one-head slice of a ``(bs, KV, D)`` block.  With
    ``cfg.kv_quant`` the pool stores int8 K/V plus per-row fp32 scale
    planes ``(num_blocks, KV, bs)`` that travel with their blocks
    through every scatter/gather (admission, preemption, prefix cache).
    Full-context GQA only (no MLA / sliding-window)."""
    assert not cfg.mla, "paged KV: GQA only (MLA caches latents)"
    out = dict(k=(num_blocks, cfg.n_kv_heads, block_size, cfg.head_dim),
               v=(num_blocks, cfg.n_kv_heads, block_size, cfg.head_dim))
    if cfg.kv_quant:
        out["k_scale"] = (num_blocks, cfg.n_kv_heads, block_size)
        out["v_scale"] = (num_blocks, cfg.n_kv_heads, block_size)
    return out


def _virtual_cache(pool, block_tables):
    """Gather each row's blocks out of one layer's ``(nblocks, KV, bs,
    ...)`` pool into the dense ``(B, KV, nb * bs, ...)`` arena layout."""
    B, nb = block_tables.shape
    g = jnp.moveaxis(pool[block_tables], 2, 1)        # (B, KV, nb, bs, ...)
    return g.reshape((B, g.shape[1], nb * g.shape[3]) + g.shape[4:])


def decode_attention_paged(q, k_pool, v_pool, block_tables, lens, layer,
                           use_pallas=False):
    """Single-token attention over a block-pooled KV cache.

    q: (B, H, D); k/v pool: the stacked (n, nblocks, KV, bs, D) pool,
    read at layer ``layer``; block_tables: (B, nb) int32 (entries past a
    sequence's allocated prefix point at trash block 0); lens: (B,)
    valid-row counts.  The jnp path gathers each sequence's blocks into a
    dense (B, KV, nb*bs, D) virtual cache and reuses the dense decode
    math — bit-identical to the dense arena when ``nb*bs`` equals the
    arena length; the Pallas path walks the block table directly (no
    gather materialization)."""
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.paged_decode_attention(q, k_pool, v_pool, block_tables,
                                           lens, layer)
    return _attend(q, _virtual_cache(k_pool[layer], block_tables),
                   _virtual_cache(v_pool[layer], block_tables), lens)


def _kv_quant(x):
    """absmax int8 quantization over the head dim.
    x: (..., hd) -> (int8 (..., hd), f32 scale (...,)).

    The scale is *floored* at 1e-8 (div-by-zero guard for all-zero rows),
    not epsilon-inflated: ``max|x|/127 + eps`` would shrink every row
    below full int8 range and near-zero rows (max|x| ~ 1e-6) would lose
    more than a bit of their mantissa to the additive term."""
    scale = jnp.maximum(
        jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0, 1e-8)
    xi = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                  -127, 127).astype(jnp.int8)
    return xi, scale.astype(jnp.float32)


def _attend_quant(q, k_i8, v_i8, k_scale, v_scale, lens):
    """Int8 counterpart of :func:`_attend` over one layer's (B, KV, S, D)
    int8 slabs and (B, KV, S) scales: the dots consume int8 operands
    (XLA fuses the widening convert, so HBM traffic is the int8 bytes);
    per-slot scales are applied to the score/probability matrices, never
    to the cache-sized tensors."""
    B, H, D = q.shape
    KV, S = k_i8.shape[1], k_i8.shape[2]
    valid_mask = jnp.arange(S)[None, :] < lens[:, None]
    qs = q.reshape(B, KV, H // KV, D)
    s = jnp.einsum("bkgd,bksd->bkgs", qs.astype(jnp.float32),
                   k_i8.astype(jnp.float32)) / np.sqrt(D)
    s = s * k_scale[:, :, None, :]                         # (B,KV,1,S)
    s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    pv = p * v_scale[:, :, None, :]
    o = jnp.einsum("bkgs,bksd->bkgd", pv, v_i8.astype(jnp.float32))
    return o.reshape(B, H, D).astype(q.dtype)


def decode_attention_quant(q, k_i8, v_i8, k_scale, v_scale, lens, layer,
                           use_pallas=False):
    """Flash-decode over an int8 KV arena.  The Pallas path fuses the
    algebra of :func:`_attend_quant` into the online-softmax decode
    kernel (see
    :func:`repro.kernels.decode_attention.decode_attention_quant_fwd`).

    q: (B, H, D); k_i8/v_i8: the stacked int8 arena (n, B, KV, S, D);
    scales: (n, B, KV, S); all read at layer ``layer``; lens: (B,)
    valid-row counts (a slot prefix, as in :func:`decode_attention`)."""
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.decode_attention_quant(q, k_i8, v_i8, k_scale, v_scale,
                                           lens, layer)
    return _attend_quant(q, k_i8[layer], v_i8[layer], k_scale[layer],
                         v_scale[layer], lens)


def decode_attention_paged_quant(q, k_pool, v_pool, k_scale, v_scale,
                                 block_tables, lens, layer, use_pallas=False):
    """Single-token attention over an int8 block-pooled KV cache.

    q: (B, H, D); k/v pool: the stacked (n, nblocks, KV, bs, D) int8
    pool; scale pools: (n, nblocks, KV, bs) fp32; all read at layer
    ``layer``; block_tables: (B, nb) int32; lens: (B,).  The jnp path
    gathers blocks *and their scale rows* into a dense virtual cache and
    reuses the dense int8 math — bit-identical to the dense int8 arena
    when ``nb*bs`` equals the arena length; the Pallas path walks the
    block table with dequant fused into the online softmax (no gather,
    no fp materialization)."""
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.paged_decode_attention_quant(
            q, k_pool, v_pool, k_scale, v_scale, block_tables, lens, layer)
    return _attend_quant(
        q, *(_virtual_cache(x[layer], block_tables)
             for x in (k_pool, v_pool, k_scale, v_scale)), lens)


def attn_apply(cfg: ModelConfig, p, x, *, positions, mode, cache=None,
               window=None, block_tables=None, layer=None):
    """mode: 'full' (train / full prefill) | 'prefill' (also fills cache) |
    'decode' (x is (B,1,D), cache holds history).

    In decode, ``cache`` is the stacked cache of the whole scan segment
    and ``layer`` this layer's index into it: the new K/V row is written
    at ``[layer, ...]`` where the stack lies and the decode kernel reads
    the layer there, so no slab of it is sliced out or copied.
    ``block_tables`` selects the **paged** decode path: ``cache`` is then
    the shared block pool (see :func:`paged_attn_cache_shape`) and each
    row's KV is read/written through its block table instead of a dense
    arena row."""
    B = x.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ wgather(p["wq"], cfg, ("embed", "heads"))).reshape(B, -1, H, hd)
    k = (x @ wgather(p["wk"], cfg, ("embed", "kv_heads"))).reshape(
        B, -1, KV, hd)
    v = (x @ wgather(p["wv"], cfg, ("embed", "kv_heads"))).reshape(
        B, -1, KV, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rms_eps)
    q = rope(cfg, q, positions)
    k = rope(cfg, k, positions)
    if cfg.softmax_mscale != 1.0:
        # YaRN's softmax scale, folded into q for every attention path
        q = q * cfg.softmax_mscale

    new_cache = None
    if mode == "decode":
        assert cache is not None and layer is not None
        pos = positions[:, 0]                       # (B,)
        if block_tables is not None:
            # paged: write the new KV row at (table[pos // bs], pos % bs)
            # and attend through the block table.  Rows past a slot's
            # allocated prefix resolve to the trash block (table padding
            # = 0); with a FULLY allocated table the clamped index instead
            # wraps post-EOS writes into the slot's own last block — dead
            # for decode (a finished slot's output is masked until
            # harvest), and the prefix cache never indexes that last
            # block, so its possibly stale rows are never reused as a
            # cached prefix.
            assert window is None, \
                "paged KV supports full-context GQA only"
            bs = cache["k"].shape[3]
            nb = block_tables.shape[1]
            bi = jnp.minimum(pos // bs, nb - 1)
            row = jnp.take_along_axis(block_tables, bi[:, None], axis=1)[:, 0]
            col = pos % bs
            lens = jnp.minimum(pos + 1, nb * bs)
        else:
            # dense: row b's entry for position t lives at slot t % S of
            # its slab (a ring buffer under a sliding window)
            S = cache["k"].shape[3]
            row, col = jnp.arange(B), pos % S
            lens = jnp.minimum(pos + 1, S)
        # one index per (row, head): the update window is the minor head
        # dim alone, so the stack keeps the row-major layout the decode
        # kernel reads (a (KV, D) window makes the TPU compiler lay the
        # stack out KV-minor and copy it back for the kernel every layer)
        heads = jnp.arange(KV)[None, :]

        def put(a, u):
            return a.at[layer, row[:, None], heads, col[:, None]].set(
                u, unique_indices=block_tables is None)

        rows = dict(k=k[:, 0], v=v[:, 0])           # (B, KV, hd) each
        if cfg.kv_quant:
            rows["k"], rows["k_scale"] = _kv_quant(k[:, 0])
            rows["v"], rows["v_scale"] = _kv_quant(v[:, 0])
        new_cache = {n: put(cache[n], r) for n, r in rows.items()}
        if block_tables is None:
            fn = decode_attention_quant if cfg.kv_quant else decode_attention
        else:
            fn = partial(decode_attention_paged_quant if cfg.kv_quant
                         else decode_attention_paged,
                         block_tables=block_tables)
        o = fn(q[:, 0], *new_cache.values(), lens=lens, layer=layer,
               use_pallas=cfg.pallas_kernels)[:, None]   # (B,1,H,hd)
    else:
        # prefix-cache suffix prefill: the cache dict may carry a
        # read-only KV history ("hk"/"hv", gathered from shared pool
        # blocks) that the current tokens attend to but never rewrite.
        # Keys are [history; current] and queries are the LAST Lq of the
        # Lk positions, which is exactly the kernels' rectangular-causal
        # convention (q_offset = Lk - Lq); RoPE is position-correct on
        # both sides (history keys were rotated at their absolute
        # positions when first written, current q/k via ``positions``).
        k_att, v_att = k, v
        if cache is not None and "hk" in cache:
            hk, hv = cache["hk"], cache["hv"]
            if cfg.kv_quant:
                # int8 history: dequantize the gathered prefix before the
                # concat — a (B, P, KV, hd) compute-side temporary, not a
                # cache write; the pool itself stays int8
                hk = (hk.astype(jnp.float32)
                      * cache["hk_scale"][..., None]).astype(k.dtype)
                hv = (hv.astype(jnp.float32)
                      * cache["hv_scale"][..., None]).astype(v.dtype)
            k_att = jnp.concatenate([hk, k], axis=1)
            v_att = jnp.concatenate([hv, v], axis=1)
        if cfg.pallas_kernels:
            from repro.kernels import ops as kops
            o = kops.flash_attention(q, k_att, v_att, causal=True,
                                     window=window)
        else:
            o = flash_attention(q, k_att, v_att, causal=True, window=window,
                                qpos0=k_att.shape[1] - q.shape[1])
        if mode == "prefill":
            assert cache is not None
            S = cache["k"].shape[2]
            L = k.shape[1]
            rows = dict(k=k, v=v)
            if cfg.kv_quant:
                rows["k"], rows["k_scale"] = _kv_quant(k)
                rows["v"], rows["v_scale"] = _kv_quant(v)
            # one transpose into the arena's KV-head-major layout; the
            # entry for position t lives at slot t % S, so past S (a
            # sliding window) the last S rows are rolled into place
            new_cache = {}
            for name, r in rows.items():
                r = jnp.swapaxes(r, 1, 2)           # (B, KV, L, ...)
                new_cache[name] = (
                    cache[name].at[:, :, :L].set(r) if L <= S else
                    jnp.roll(r[:, :, -S:], (L - S) % S, axis=2))
    out = o.reshape(B, -1, H * hd) @ wgather(p["wo"], cfg,
                                            ("heads", "embed"))
    return out, new_cache


# ===================================================================== #
# MLA (DeepSeek-V2 multi-head latent attention)
# ===================================================================== #
def mla_specs(cfg: ModelConfig) -> dict:
    D, H = cfg.d_model, cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    return {
        "wq": ParamSpec((D, H * (dn + dr)), ("embed", "heads")),
        "w_dkv": ParamSpec((D, r + dr), ("embed", None)),
        "kv_norm": ParamSpec((r,), (None,), "ones"),
        "w_uk": ParamSpec((r, H * dn), (None, "heads")),
        "w_uv": ParamSpec((r, H * dv), (None, "heads")),
        "wo": ParamSpec((H * dv, D), ("heads", "embed")),
    }


def mla_cache_shape(cfg: ModelConfig, batch: int, max_len: int):
    return dict(ckv=(batch, max_len, cfg.kv_lora_rank),
                krope=(batch, max_len, cfg.qk_rope_head_dim))


def _mla_qkv(cfg, p, x, positions):
    B, L, _ = x.shape
    H = cfg.n_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ wgather(p["wq"], cfg, ("embed", "heads"))).reshape(
        B, L, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = rope(cfg, q_rope, positions)
    dkv = x @ wgather(p["w_dkv"], cfg, ("embed", None))   # (B,L,r+dr)
    ckv = rmsnorm(dkv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.rms_eps)
    krope = rope(cfg, dkv[..., cfg.kv_lora_rank:], positions)
    return q_nope, q_rope, ckv, krope


def mla_apply(cfg: ModelConfig, p, x, *, positions, mode, cache=None,
              window=None, block_tables=None):
    """MLA.  Prefill/train: expand compressed KV and run flash attention
    (the Pallas kernels under ``cfg.pallas_kernels``).  Decode: *absorbed*
    form — scores and values computed directly against the compressed
    cache (W_UK folded into q, W_UV applied after), so the per-token cost
    is O(L·(r+dr)) instead of O(L·H·(dn+dr)).  Every path scales the
    scores by ``cfg.softmax_scale(dn + dr)`` (YaRN's, where set)."""
    assert block_tables is None, "paged KV does not support MLA"
    B = x.shape[0]
    H = cfg.n_heads
    r, dn, dr, dv = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    q_nope, q_rope, ckv, krope = _mla_qkv(cfg, p, x, positions)
    scale = cfg.softmax_scale(dn + dr)
    new_cache = None

    if mode == "decode":
        assert cache is not None
        S = cache["ckv"].shape[1]
        pos = positions[:, 0]
        ckv_c = jax.vmap(lambda c, i, u: c.at[i].set(u))(
            cache["ckv"], pos % S, ckv[:, 0])
        krope_c = jax.vmap(lambda c, i, u: c.at[i].set(u))(
            cache["krope"], pos % S, krope[:, 0])
        valid = jnp.arange(S)[None] < jnp.minimum(pos + 1, S)[:, None]
        # absorbed scores
        w_uk = p["w_uk"].reshape(r, H, dn)
        q_eff = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)     # (B,H,r)
        s = (jnp.einsum("bhr,bsr->bhs", q_eff, ckv_c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhd,bsd->bhs", q_rope[:, 0], krope_c,
                          preferred_element_type=jnp.float32)) * scale
        s = jnp.where(valid[:, None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bhs,bsr->bhr", pr.astype(ckv_c.dtype), ckv_c,
                         preferred_element_type=jnp.float32)       # (B,H,r)
        w_uv = p["w_uv"].reshape(r, H, dv)
        o = jnp.einsum("bhr,rhd->bhd", ctx.astype(x.dtype), w_uv)
        o = o[:, None]                                             # (B,1,H,dv)
        new_cache = dict(ckv=ckv_c, krope=krope_c)
    else:
        L = x.shape[1]
        k_nope = (ckv @ p["w_uk"]).reshape(B, L, H, dn)
        vfull = (ckv @ p["w_uv"]).reshape(B, L, H, dv)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope[:, :, None], (B, L, H, dr))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        # pad V to qk head dim so flash kernel sees uniform D, slice after
        dq = dn + dr
        vpad = jnp.pad(vfull, ((0, 0), (0, 0), (0, 0), (0, dq - dv))) \
            if dq != dv else vfull
        if cfg.pallas_kernels:
            from repro.kernels import ops as kops
            o = kops.flash_attention(q, k, vpad, causal=True, window=window,
                                     sm_scale=scale)
        else:
            o = flash_attention(q, k, vpad, causal=True, window=window,
                                scale=scale)
        o = o[..., :dv]
        if mode == "prefill":
            S = cache["ckv"].shape[1]
            new_cache = dict(ckv=cache["ckv"].at[:, :L].set(ckv),
                             krope=cache["krope"].at[:, :L].set(krope))
    out = o.reshape(B, -1, H * dv) @ wgather(p["wo"], cfg,
                                             ("heads", "embed"))
    return out, new_cache


# ===================================================================== #
# Cross-attention (VLM/audio encoder embeddings; KV cached once per image)
# ===================================================================== #
def cross_attn_specs(cfg: ModelConfig) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": ParamSpec((D, H * hd), ("embed", "heads")),
        "wk": ParamSpec((D, KV * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((D, KV * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((H * hd, D), ("heads", "embed")),
        "gate": ParamSpec((1,), (None,), "zeros"),   # llama3.2-v tanh gate
    }


def cross_attn_apply(cfg: ModelConfig, p, x, enc, *, mode="full",
                     cache=None):
    """x: (B,L,D) queries; enc: (B,Le,D) projected encoder states.
    In decode mode the K/V of the encoder come precomputed from ``cache``
    (filled at prefill — image K/V lives outside the decode hot loop)."""
    B, L, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, L, H, hd)
    if mode == "decode":
        assert cache is not None
        k, v = cache["xk"], cache["xv"]
        new_cache = cache
    else:
        k = (enc @ p["wk"]).reshape(B, -1, KV, hd)
        v = (enc @ p["wv"]).reshape(B, -1, KV, hd)
        new_cache = dict(xk=k.astype(cache["xk"].dtype),
                         xv=v.astype(cache["xv"].dtype)) \
            if cache is not None else dict(xk=k, xv=v)
    o = flash_attention(q, k, v, causal=False)
    out = (o.reshape(B, L, H * hd) @ p["wo"])
    out = jnp.tanh(p["gate"].astype(jnp.float32)).astype(out.dtype) * out
    return out, new_cache


# ===================================================================== #
# Dense SwiGLU MLP
# ===================================================================== #
def mlp_specs(cfg: ModelConfig, d_ff=None) -> dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    return {
        "w_gate": ParamSpec((D, F), ("embed", "mlp")),
        "w_up": ParamSpec((D, F), ("embed", "mlp")),
        "w_down": ParamSpec((F, D), ("mlp", "embed")),
    }


def mlp_apply(p, x, cfg=None):
    wg = (lambda w, axes: wgather(w, cfg, axes)) if cfg is not None \
        else (lambda w, axes: w)
    h = (jax.nn.silu(x @ wg(p["w_gate"], ("embed", "mlp")))
         * (x @ wg(p["w_up"], ("embed", "mlp"))))
    return h @ wg(p["w_down"], ("mlp", "embed"))


# ===================================================================== #
# MoE: dropless routing over the experts this model holds
# ===================================================================== #
def moe_specs(cfg: ModelConfig) -> dict:
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    s = {
        "router": ParamSpec((D, cfg.n_router), ("embed", None)),
        "w_gate": ParamSpec((E, D, F), ("experts", "embed", "mlp")),
        "w_up": ParamSpec((E, D, F), ("experts", "embed", "mlp")),
        "w_down": ParamSpec((E, F, D), ("experts", "mlp", "embed")),
    }
    if cfg.n_shared_experts:
        s["shared"] = mlp_specs(cfg, d_ff=cfg.n_shared_experts * cfg.moe_d_ff)
    return s


def grouped_matmul(x, w, sizes, use_pallas=False, layer=None):
    """Rows of ``x`` (M, K), sorted by group, times their group's matrix
    of ``w`` (G, K, N): group g is the ``sizes[g]`` rows after those of
    the groups before it.  Rows past ``sizes.sum()`` are not computed;
    their output rows hold no defined value.

    With ``layer``, ``w`` is (U, G, K, N), the matrices of every layer of
    a scan, and the groups are layer ``layer``'s.  The Pallas kernel then
    reads them where they lie in the stack: it is handed the whole stack
    as U * G groups, all empty but the layer's, so no slice of the stack
    is copied out for it."""
    if not use_pallas:
        if layer is not None:
            w = jax.lax.dynamic_index_in_dim(w, layer, 0, False)
        return jax.lax.ragged_dot(x, w, sizes)
    from repro.kernels import ops as kops
    if layer is not None:
        U, G = w.shape[:2]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((U * G,), jnp.int32), sizes.astype(jnp.int32),
            (layer * G,))
        w = w.reshape(U * G, *w.shape[2:])
    return kops.grouped_matmul(x, w, sizes)


def moe_apply(cfg: ModelConfig, p, x, layer=None):
    """x: (B,L,D) -> (out, aux_loss, counts).  Dropless routing over the
    experts this model holds.  With ``layer``, ``p``'s expert weights are
    stacked over a scan's layers and this is layer ``layer``
    (:func:`grouped_matmul`).

    A softmax router over all ``cfg.n_router`` experts picks each token's
    top-k (gates renormalised over the k when ``cfg.norm_topk``).  The
    slots that chose one of the first ``cfg.n_experts`` experts, whose
    weights are held here, are grouped by expert and run through grouped
    matmuls; each output, scaled by its gate, is added to its token, and
    the shared experts are added to every token.  Slots routed to experts
    held elsewhere contribute nothing here: an expert-parallel layer sums
    the shares of all its chips.  No slot is dropped at any skew: the
    grouped rows are bounded by the worst case, T x min(k, held), and
    the rows past the groups are not computed.  ``counts`` is int32
    ``(held slots, held experts that got a slot)``."""
    B, L, D = x.shape
    T = B * L
    E, R, K = cfg.n_experts, cfg.n_router, cfg.top_k
    xf = x.reshape(T, D)
    logits = (xf @ p["router"]).astype(jnp.float32)             # (T,R)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)             # (T,K)
    if cfg.norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

    # each held slot's row in the grouped order: the rows of expert e
    # start after those of experts 0..e-1, in slot order
    n_rows = T * min(K, E)
    e_flat = expert_idx.reshape(T * K)
    held = e_flat < E
    onehot = (e_flat[:, None] == jnp.arange(E)).astype(jnp.int32)  # (T*K,E)
    sizes = onehot.sum(0)
    rank = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
    start = jnp.cumsum(sizes) - sizes
    dest = jnp.where(held, start[jnp.minimum(e_flat, E - 1)] + rank, n_rows)
    # the token of each grouped row (T: the zero row past the tokens)
    rows = jnp.full((n_rows,), T, jnp.int32).at[dest].set(
        jnp.arange(T * K, dtype=jnp.int32) // K, mode="drop")
    xs = jnp.take(jnp.concatenate([xf, jnp.zeros((1, D), x.dtype)]), rows,
                  axis=0)
    lead = () if layer is None else (None,)
    w_g = wgather(p["w_gate"], cfg, lead + ("experts", "embed", "mlp"))
    w_u = wgather(p["w_up"], cfg, lead + ("experts", "embed", "mlp"))
    w_d = wgather(p["w_down"], cfg, lead + ("experts", "mlp", "embed"))
    gmm = partial(grouped_matmul, sizes=sizes, use_pallas=cfg.pallas_kernels,
                  layer=layer)
    h = jax.nn.silu(gmm(xs, w_g)) * gmm(xs, w_u)
    y = gmm(h, w_d)                                             # (n_rows,D)
    # back to the slots (a slot held elsewhere reads the zero row), then
    # each token's slots weighted by their gates
    y = jnp.concatenate([y.astype(x.dtype), jnp.zeros((1, D), x.dtype)])
    ys = jnp.take(y, jnp.minimum(dest, n_rows), axis=0)         # (T*K,D)
    w = jnp.where(held, gate_vals.reshape(T * K), 0.0).astype(x.dtype)
    out = (ys * w[:, None]).reshape(T, K, D).sum(1)

    if cfg.n_shared_experts:
        out = out + mlp_apply(p["shared"], xf, cfg)

    # aux losses over the router's experts: switch-style load balance +
    # router z-loss
    frac = jax.nn.one_hot(expert_idx, R, dtype=jnp.int32).sum(1).mean(
        0).astype(jnp.float32)                                  # (R,)
    imp = probs.mean(0)                                         # (R,)
    aux = (cfg.router_aux_coef * R * jnp.sum(frac * imp) / K
           + cfg.router_z_coef
           * jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2))
    counts = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return out.reshape(B, L, D), aux, counts


# ===================================================================== #
# Mamba2 (SSD) mixer
# ===================================================================== #
def ssm_specs(cfg: ModelConfig) -> dict:
    D = cfg.d_model
    di, nh, N, G = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_state, cfg.ssm_ngroups
    conv_dim = di + 2 * G * N
    in_dim = 2 * di + 2 * G * N + nh
    return {
        "w_in": ParamSpec((D, in_dim), ("embed", "mlp")),
        "conv_w": ParamSpec((cfg.ssm_conv, conv_dim), (None, "mlp")),
        "conv_b": ParamSpec((conv_dim,), ("mlp",), "zeros"),
        "A_log": ParamSpec((nh,), (None,), "zeros"),
        "D_skip": ParamSpec((nh,), (None,), "ones"),
        "dt_bias": ParamSpec((nh,), (None,), "zeros"),
        "out_norm": ParamSpec((di,), ("mlp",), "ones"),
        "w_out": ParamSpec((di, D), ("mlp", "embed")),
    }


def segsum(x):
    """x: (..., q) -> (..., q, q) lower-triangular segment sums."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, -1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(X, dt, A, B, C, chunk, initial_state=None, use_pallas=False):
    """Chunked SSD scan (Mamba2 eq. via state-space duality).

    X: (b,l,h,p)  dt: (b,l,h)  A: (h,)  B,C: (b,l,n)  [ngroups=1, shared]
    Returns (Y: (b,l,h,p), final_state: (b,h,p,n)).
    """
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.ssd_scan(X, dt, A, B, C, chunk, initial_state)
    b, l, h, p = X.shape
    n = B.shape[-1]
    q = min(chunk, l)
    nc = -(-l // q)
    pad = nc * q - l
    if pad:
        X = jnp.pad(X, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    Xc = X.reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h)
    Bc = B.reshape(b, nc, q, n)
    Cc = C.reshape(b, nc, q, n)
    dA = dtc * A[None, None, None, :]                    # (b,c,q,h)
    dA = jnp.moveaxis(dA, 3, 2)                          # (b,c,h,q)
    Xd = Xc * dtc[..., None]                             # dt-discretized input

    A_cs = jnp.cumsum(dA, -1)                            # (b,c,h,q)
    Ldec = jnp.exp(segsum(dA))                           # (b,c,h,q,q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)
    Y_diag = jnp.einsum("bcqk,bchqk,bckhp->bcqhp", scores, Ldec, Xd)

    # per-chunk end states
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)        # (b,c,h,q)
    S_c = jnp.einsum("bchq,bcqn,bcqhp->bchpn", decay_states, Bc, Xd)
    chunk_decay = jnp.exp(A_cs[..., -1])                 # (b,c,h)

    def step(s, xs):
        sc, dec = xs
        s_out = s                                        # state entering chunk
        s_next = s * dec[..., None, None] + sc
        return s_next, s_out

    s0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((b, h, p, n), jnp.float32))
    S_c = S_c.astype(jnp.float32)
    final, states_in = jax.lax.scan(
        step, s0, (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    states_in = jnp.moveaxis(states_in, 0, 1)            # (b,c,h,p,n)
    Y_off = jnp.einsum("bcqn,bchpn,bchq->bcqhp", Cc, states_in,
                       jnp.exp(A_cs))
    Y = (Y_diag + Y_off).reshape(b, nc * q, h, p)[:, :l]
    return Y, final


def ssm_cache_shape(cfg: ModelConfig, batch: int):
    di, nh, N, G = (cfg.d_inner, cfg.ssm_nheads, cfg.ssm_state,
                    cfg.ssm_ngroups)
    conv_dim = di + 2 * G * N
    return dict(conv=(batch, cfg.ssm_conv - 1, conv_dim),
                state=(batch, nh, cfg.ssm_headdim, N))


def ssm_apply(cfg: ModelConfig, p, x, *, mode, cache=None):
    """Mamba2 block.  'full'/'prefill': chunked SSD; 'decode': O(1) step."""
    B = x.shape[0]
    di, nh, hp, N = (cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim,
                     cfg.ssm_state)
    G = cfg.ssm_ngroups
    conv_dim = di + 2 * G * N
    zxbcdt = x @ wgather(p["w_in"], cfg, ("embed", "mlp"))
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + conv_dim]
    dt_raw = zxbcdt[..., di + conv_dim:]
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    new_cache = None

    if mode == "decode":
        assert cache is not None
        win = jnp.concatenate([cache["conv"], xbc], axis=1)   # (B,K,conv_dim)
        conv_out = jnp.einsum("bkc,kc->bc", win, p["conv_w"]) + p["conv_b"]
        conv_out = jax.nn.silu(conv_out)
        new_conv = win[:, 1:]
        xi = conv_out[..., :di].reshape(B, nh, hp)
        Bv = conv_out[..., di:di + N]
        Cv = conv_out[..., di + N:di + 2 * N]
        dt1 = dt[:, 0]                                        # (B,nh)
        dA = jnp.exp(dt1 * A[None])                           # (B,nh)
        dBx = jnp.einsum("bhp,bn->bhpn", xi * dt1[..., None], Bv)
        state = cache["state"] * dA[..., None, None] + dBx.astype(
            cache["state"].dtype)
        y = jnp.einsum("bhpn,bn->bhp", state, Cv)
        y = y + p["D_skip"][None, :, None] * xi
        y = y.reshape(B, 1, di)
        z = z
        new_cache = dict(conv=new_conv, state=state)
    else:
        L = x.shape[1]
        # causal depthwise conv via padding + windowed dot
        K = cfg.ssm_conv
        xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
        idx = jnp.arange(L)[:, None] + jnp.arange(K)[None, :]
        win = xp[:, idx]                                      # (B,L,K,conv)
        conv_out = jax.nn.silu(
            jnp.einsum("blkc,kc->blc", win, p["conv_w"]) + p["conv_b"])
        xi = conv_out[..., :di].reshape(B, L, nh, hp)
        Bv = conv_out[..., di:di + N]
        Cv = conv_out[..., di + N:di + 2 * N]
        # TP over SSD heads: the (b,c,h,q,q) decay tensors are the memory
        # peak of Mamba2 training and shard cleanly on h
        xi = constrain_axis(xi, cfg, 2)
        dt = constrain_axis(dt, cfg, 2)
        Y, final = ssd_chunked(xi, dt, A, Bv, Cv, cfg.ssm_chunk,
                               use_pallas=cfg.pallas_kernels)
        Y = Y + p["D_skip"][None, None, :, None] * xi
        y = Y.reshape(B, L, di)
        if mode == "prefill":
            new_conv = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))[:, -(K - 1):]
            new_cache = dict(conv=new_conv,
                             state=final.astype(cache["state"].dtype)
                             if cache else final)
    y = rmsnorm_gated(y.astype(x.dtype), z, p["out_norm"], cfg.rms_eps)
    return (y @ wgather(p["w_out"], cfg, ("mlp", "embed"))
            ).astype(x.dtype), new_cache
